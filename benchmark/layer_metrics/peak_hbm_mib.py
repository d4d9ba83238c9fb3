"""Peak device memory on the fullest chip after the window: allocator peak
plus the runtime's reservation for program temporaries."""
LAYER = "device"
UNIT, SOURCE, MOVES = "MiB", "program_counter", "rounds_per_s"


def read(ctx):
    peak = ctx["device"].get("memory_peak_bytes", 0)
    return peak / 2 ** 20 if peak else None
