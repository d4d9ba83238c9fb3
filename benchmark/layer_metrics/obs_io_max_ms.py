"""The longest single span of the observers' own work in the timed window,
milliseconds: `obs/heartbeat_write`, `obs/flight_write`, `obs/memory_poll`
and `metrics/emit` (the drain thread's row writes). With two units in
flight, one such stall longer than a unit starves the device. Prints the
longest of each as `[bench] obs_io {...}`."""
import json

from benchmark import program_view

LAYER = "engine"
UNIT, SOURCE, MOVES = "ms", "program_span", "rounds_per_s"


def read(ctx):
    tr = program_view.tracer()
    bounds = (program_view.phase_bounds(ctx, "window")
              if tr is not None else None)
    if bounds is None:
        return None
    longest = {}
    for s in tr.records():
        if (bounds[0] <= s.start <= bounds[1]
                and (s.name.startswith("obs/") or s.name == "metrics/emit")):
            longest[s.name] = max(longest.get(s.name, 0.0),
                                  1e3 * program_view.seconds(s))
    if not longest:
        return None
    print("[bench] obs_io " + json.dumps({"max_ms": longest}), flush=True)
    return max(longest.values())
