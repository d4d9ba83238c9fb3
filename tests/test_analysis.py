"""Static-analysis subsystem (analysis/): AST rules, jaxpr contracts,
fingerprint audit, CLI exit codes, and the pinned collective baseline.

Each AST rule gets a tripping synthetic snippet AND a clean twin (the
rule must fire on the bug and stay quiet on the idiom); the jaxpr
contracts get a deliberately-broken toy program; the audit gets a
planted unlisted config field. The repo-wide scans double as the
permanent regression gate: the tree must stay finding-free."""

import json
import os
import subprocess
import sys
import textwrap

import jax
import pytest

from defending_against_backdoors_with_robust_learning_rate_tpu.analysis import (
    ast_rules, contracts, coverage, fingerprint_audit, jaxpr_lint,
    thread_rules)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


# --------------------------------------------------------------------------
# AST rules: synthetic snippets
# --------------------------------------------------------------------------

def _scan_snippet(tmp_path, source, relpath="scripts/profile_round.py"):
    """Lint `source` as if it lived at `relpath` inside a repo."""
    path = tmp_path / relpath
    path.parent.mkdir(parents=True, exist_ok=True)
    path.write_text(textwrap.dedent(source))
    return ast_rules.scan([str(path)], str(tmp_path))


def _rules(findings):
    return sorted({f.rule for f in findings})


def test_host_sync_trips_and_clean_twin(tmp_path):
    bad = """
    import jax
    import numpy as np

    def eval_loop(metrics, params):
        v = float(metrics)
        w = np.asarray(params)
        x = metrics.item()
        y = jax.device_get(metrics)
        return v, w, x, y
    """
    assert _rules(_scan_snippet(tmp_path, bad)) == ["host-sync"]
    assert len(_scan_snippet(tmp_path, bad)) == 4

    clean = """
    def eval_loop(cfg, metrics):
        thr = float(cfg.robustLR_threshold)   # config scalar: trace-time
        k = float(1e-3)                       # literal
        return thr + k
    """
    assert _scan_snippet(tmp_path, clean) == []


def test_host_sync_scoped_to_hot_modules(tmp_path):
    src = """
    def anywhere(x):
        return float(x)
    """
    # same code outside the hot-path list is not flagged
    assert _scan_snippet(tmp_path, src, relpath="scripts/plot_curves.py") \
        == []
    assert _rules(_scan_snippet(tmp_path, src)) == ["host-sync"]


def test_jit_side_effect_trips_and_clean_twin(tmp_path):
    bad = """
    import time
    import jax

    @jax.jit
    def step(x):
        print("tracing!")
        t = time.perf_counter()
        return x + t
    """
    f = _scan_snippet(tmp_path, bad, relpath="pkg/mod.py")
    assert _rules(f) == ["jit-side-effect"] and len(f) == 2

    clean = """
    import time
    import jax

    def host_loop(x):            # not traced: side effects are fine
        print("round", x)
        return time.perf_counter()

    @jax.jit
    def step(x):
        jax.debug.print("x={x}", x=x)   # the sanctioned in-jit print
        return x + 1
    """
    assert _scan_snippet(tmp_path, clean, relpath="pkg/mod.py") == []


def test_jit_side_effect_via_transform_argument(tmp_path):
    src = """
    import os
    import jax

    def body(c, x):
        flag = os.environ.get("X")      # traced via lax.scan(body, ...)
        return c, x

    def run(xs):
        return jax.lax.scan(body, 0, xs)
    """
    f = _scan_snippet(tmp_path, src, relpath="pkg/mod.py")
    assert _rules(f) == ["jit-side-effect"]


def test_jit_side_effect_closure_list_mutation(tmp_path):
    bad = """
    import jax

    def make_step():
        leaked = []

        def step(x):             # nested in a make_ builder -> traced
            leaked.append(x)     # closure mutation: trace-time only
            return x + 1
        return step
    """
    assert _rules(_scan_snippet(tmp_path, bad, relpath="pkg/mod.py")) \
        == ["jit-side-effect"]

    clean = """
    import jax

    def make_step():
        def step(xs):
            ys = []
            for i in range(3):
                ys.append(xs[i])   # local accumulation: fine
            return ys
        return step
    """
    assert _scan_snippet(tmp_path, clean, relpath="pkg/mod.py") == []


def test_prng_reuse_trips_and_rotation_is_clean(tmp_path):
    bad = """
    import jax

    def draw(key, shape):
        a = jax.random.uniform(key, shape)
        b = jax.random.normal(key, shape)    # same key consumed twice
        return a + b
    """
    assert _rules(_scan_snippet(tmp_path, bad, relpath="pkg/mod.py")) \
        == ["prng-reuse"]

    clean = """
    import jax

    def draw(key, shape):
        k1, k2 = jax.random.split(key)
        a = jax.random.uniform(k1, shape)
        b = jax.random.normal(k2, shape)
        return a + b

    def rotate(key, n):
        out = []
        for _ in range(n):
            key, sub = jax.random.split(key)   # rotation idiom
            out.append(jax.random.uniform(sub, ()))
        return out
    """
    assert _scan_snippet(tmp_path, clean, relpath="pkg/mod.py") == []


def test_prng_unused_split_trips_and_closure_use_is_clean(tmp_path):
    bad = """
    import jax

    def draw(key):
        k1, k2 = jax.random.split(key)
        return jax.random.uniform(k1, ())    # k2 is dead entropy
    """
    assert _rules(_scan_snippet(tmp_path, bad, relpath="pkg/mod.py")) \
        == ["prng-unused-split"]

    clean = """
    import jax

    def draw(key):
        k1, k2 = jax.random.split(key)

        def inner(b):
            return jax.random.fold_in(k2, b)   # closure use counts
        return jax.random.uniform(k1, ()), inner
    """
    assert _scan_snippet(tmp_path, clean, relpath="pkg/mod.py") == []


def test_donate_reuse_trips_and_rebind_is_clean(tmp_path):
    bad = """
    import functools
    import jax

    @functools.partial(jax.jit, donate_argnums=0)
    def step(params, x):
        return params, x

    def loop(params, xs):
        out, _ = step(params, xs)
        return params            # donated buffer read after the call
    """
    assert _rules(_scan_snippet(tmp_path, bad, relpath="pkg/mod.py")) \
        == ["donate-reuse"]

    clean = """
    import functools
    import jax

    @functools.partial(jax.jit, donate_argnums=0)
    def step(params, x):
        return params, x

    def loop(params, xs):
        params, _ = step(params, xs)   # rebound on the call line
        return params
    """
    assert _scan_snippet(tmp_path, clean, relpath="pkg/mod.py") == []


def test_pragma_and_allow_suppression(tmp_path):
    src = """
    def eval_loop(metrics):
        # static: ok(host-sync)
        v = float(metrics)
        w = metrics.item()    # not covered by the pragma above
        return v + w
    """
    f = _scan_snippet(tmp_path, src)
    assert len(f) == 1 and f[0].rule == "host-sync"
    assert "item" in f[0].message


def test_repo_ast_scan_is_clean():
    """Satellite contract: the tree stays finding-free. A new finding
    here means either fix the code or add a justified ALLOW/pragma."""
    findings = ast_rules.scan_repo(REPO)
    assert findings == [], "\n".join(str(f) for f in findings)


# --------------------------------------------------------------------------
# fingerprint audit
# --------------------------------------------------------------------------

def test_audit_clean_on_tree():
    assert fingerprint_audit.audit(REPO) == []


def test_audit_catches_planted_unlisted_field():
    prov = fingerprint_audit.field_provenance()
    fields = fingerprint_audit.config_fields() | {"new_knob"}
    f = fingerprint_audit.audit(REPO, fields=fields, provenance=prov)
    assert len(f) == 1 and "new_knob" in f[0].message
    assert "provenance" in f[0].message


def test_audit_catches_program_field_excluded():
    prov = fingerprint_audit.field_provenance()
    excl = fingerprint_audit.excluded_fields() | {"bs"}   # program field!
    f = fingerprint_audit.audit(REPO, excluded=excl)
    msgs = "\n".join(x.message for x in f)
    assert any("'bs'" in x.message and "EXCLUDED_FIELDS" in x.message
               for x in f), msgs


def test_audit_catches_runtime_field_fingerprinted():
    excl = fingerprint_audit.excluded_fields() - {"top_frac"}
    f = fingerprint_audit.audit(REPO, excluded=excl)
    assert any("'top_frac'" in x.message and "fingerprinted" in x.message
               for x in f)


def test_audit_catches_runtime_tag_on_program_read_field():
    prov = dict(fingerprint_audit.field_provenance())
    prov["bs"] = "runtime"   # bs is read by fl/client.py's builder
    f = fingerprint_audit.audit(REPO, provenance=prov)
    assert any("'bs'" in x.message and "program-shaping" in x.message
               for x in f)


def test_property_reads_map_to_fields():
    cfg_path = os.path.join(REPO, contracts.PKG, "config.py")
    props = fingerprint_audit.property_field_map(cfg_path)
    # cohort_size joined in ISSUE 7: an explicit cohort size overrides
    # the legacy floor(K * C) product
    assert props["agents_per_round"] == {"num_agents", "agent_frac",
                                         "cohort_size"}
    assert "dropout_rate" in props["faults_enabled"]
    reads = fingerprint_audit.program_field_reads(REPO)
    # fl/rounds reads cfg.agents_per_round -> both underlying fields seen
    assert "num_agents" in reads and "agent_frac" in reads


# --------------------------------------------------------------------------
# jaxpr contracts
# --------------------------------------------------------------------------

def test_collective_counting_on_toy_shard_map():
    import numpy as np
    import jax.numpy as jnp
    from jax.sharding import Mesh, PartitionSpec as P
    from jax import shard_map
    mesh = Mesh(np.array(jax.devices()[:8]), ("agents",))

    def body(x):
        s = jax.lax.psum(jnp.sum(x), "agents")
        t = jax.lax.psum(jnp.sum(x * 2), "agents")
        g = jax.lax.all_gather(x, "agents", axis=0, tiled=True)
        return s + t + jnp.sum(g)

    f = jax.jit(shard_map(body, mesh=mesh, in_specs=(P("agents"),),
                          out_specs=P(), check_vma=False))
    from defending_against_backdoors_with_robust_learning_rate_tpu.utils import (
        compile_cache)
    closed = compile_cache.trace_program(
        f, (jax.ShapeDtypeStruct((8, 4), jnp.float32),))
    counts = jaxpr_lint.collective_counts(closed)
    assert counts["psum"] == 2 and counts["all_gather"] == 1


def test_hlo_collective_counts_array_and_tuple_results():
    """Optimized-HLO counting: a single-array result type, and the tuple
    type XLA's combiner gives a merged op (jaxlib 0.9.0's XLA:CPU merges
    the per-leaf psums into one tuple-typed all-reduce — an op the
    counter cannot see makes every compiled ceiling vacuous)."""
    hlo = """
  %psum.7 = f32[] all-reduce(%wrapped_reduce), channel_id=1, to_apply=%r
  %all-reduce = (f32[32]{0}, f32[3,3,1,32]{3,2,1,0}, /*index=2*/f32[3]{0}) all-reduce(%a, %b, %c), channel_id=2
  %get-tuple-element.1 = f32[32]{0} get-tuple-element(%all-reduce), index=0
  %ag = f32[8,4]{1,0} all-gather(%param.1), channel_id=3, dimensions={0}
  %ars = f32[4]{0} all-reduce-start(%x), channel_id=4
  %ard = f32[4]{0} all-reduce-done(%ars)
"""
    assert jaxpr_lint.hlo_collective_counts(hlo) == {
        "all-reduce": 3, "all-gather": 1}


def test_forbidden_primitive_detected_on_broken_toy():
    import jax.numpy as jnp

    @jax.jit
    def leaky(x):
        jax.debug.print("x={x}", x=x)   # debug_print: forbidden
        return x + 1

    from defending_against_backdoors_with_robust_learning_rate_tpu.utils import (
        compile_cache)
    closed = compile_cache.trace_program(
        leaky, (jax.ShapeDtypeStruct((4,), jnp.float32),))
    sites = jaxpr_lint.forbidden_sites(closed)
    assert sites and "debug_print" in sites[0]
    assert jaxpr_lint.forbidden_sites(
        compile_cache.trace_program(
            jax.jit(lambda x: x + 1),
            (jax.ShapeDtypeStruct((4,), jnp.float32),))) == []


def test_budget_violation_fails_and_within_budget_passes(monkeypatch):
    """A deliberately tightened budget must produce a collective-budget
    finding; the real budget must not."""
    specs = contracts.check_specs()
    ok = specs["sharded_rlr_avg"]
    findings, record = jaxpr_lint.check_family(ok)
    assert findings == []
    assert record["collectives"]["psum"] == ok.collective_budget["psum"]

    import dataclasses
    broken = dataclasses.replace(
        ok, collective_budget={**ok.collective_budget,
                               "psum": ok.collective_budget["psum"] - 1})
    findings, _ = jaxpr_lint.check_family(broken)
    assert len(findings) == 1 and findings[0].rule == "collective-budget"


def test_vmap_family_has_zero_collectives():
    findings, record = jaxpr_lint.check_family(
        contracts.check_specs()["vmap_rlr_avg"])
    assert findings == []
    assert record["collectives"] == {}


def test_telemetry_off_is_inert():
    assert jaxpr_lint.telemetry_off_findings(sharded=False) == []


def test_telemetry_on_would_trip_the_tripwire(monkeypatch):
    """Inverse control: the tripwire actually guards the telemetry call
    path (a telemetry=basic trace must hit it)."""
    from defending_against_backdoors_with_robust_learning_rate_tpu.obs import (
        telemetry)
    import dataclasses
    spec = contracts.check_specs()["vmap_rlr_avg"]
    spec_on = dataclasses.replace(
        spec, cfg_overrides={**spec.cfg_overrides, "telemetry": "basic"})

    def tripwire(*a, **k):
        raise AssertionError("tripwire")

    monkeypatch.setattr(telemetry, "compute", tripwire)
    from defending_against_backdoors_with_robust_learning_rate_tpu.utils import (
        compile_cache)
    jit_obj, example_args = jaxpr_lint.build_family(spec_on)
    with pytest.raises(AssertionError, match="tripwire"):
        compile_cache.trace_program(jit_obj, example_args)


def test_sharded_collective_counts_match_pinned_baseline():
    """ISSUE-4 acceptance: the shard_map round-family collective counts
    are pinned in analysis_baseline.json and asserted in tier-1 (exact
    when the jax version matches; the budgets gate regardless)."""
    path = jaxpr_lint.baseline_path(REPO)
    assert os.path.exists(path), "analysis_baseline.json missing"
    with open(path) as f:
        pinned = json.load(f)
    for name in ("sharded_rlr_avg", "sharded_rlr_sign",
                 "sharded_rlr_avg_faults", "sharded_rlr_sign_tel_full"):
        spec = contracts.check_specs()[name]
        findings, record = jaxpr_lint.check_family(spec)
        assert findings == [], findings
        if pinned.get("jax") == jax.__version__:
            assert record["collectives"] == \
                pinned["families"][name]["collectives"], name


def test_sign_vote_psum_sharing():
    """The collective-budget fix this PR landed: sign + RLR share one
    sign psum per leaf (n_leaves + 1 total with the loss pmean), not the
    old 2n + 1."""
    _, record = jaxpr_lint.check_family(
        contracts.check_specs()["sharded_rlr_sign"])
    n_leaves = 8
    assert record["collectives"]["psum"] == n_leaves + 1


def test_telemetry_full_shares_the_vote_psums():
    """ISSUE-5 satellite: the --telemetry full families are in the
    checked matrix, and full telemetry adds ZERO psums (its vote-margin
    histogram reads the RLR vote's own sign psums via `sign_sums`) plus
    exactly 3 tiny all_gathers (norms + the two cosine accumulators)."""
    specs = contracts.check_specs()
    _, plain_avg = jaxpr_lint.check_family(specs["sharded_rlr_avg"])
    f, tel_avg = jaxpr_lint.check_family(specs["sharded_rlr_avg_tel_full"])
    assert f == []
    assert tel_avg["collectives"]["psum"] == \
        plain_avg["collectives"]["psum"]
    assert tel_avg["collectives"]["all_gather"] == 3

    _, plain_sign = jaxpr_lint.check_family(specs["sharded_rlr_sign"])
    f, tel_sign = jaxpr_lint.check_family(
        specs["sharded_rlr_sign_tel_full"])
    assert f == []
    assert tel_sign["collectives"]["psum"] == \
        plain_sign["collectives"]["psum"]   # still n_leaves + 1, shared
    assert tel_sign["collectives"]["all_gather"] == 3

    # the vmap path stays collective-free even at full telemetry
    f, rec = jaxpr_lint.check_family(specs["vmap_rlr_avg_tel_full"])
    assert f == [] and rec["collectives"] == {}


def test_faults_adds_exactly_one_all_gather():
    _, plain = jaxpr_lint.check_family(
        contracts.check_specs()["sharded_rlr_avg"])
    _, faults = jaxpr_lint.check_family(
        contracts.check_specs()["sharded_rlr_avg_faults"])
    assert plain["collectives"].get("all_gather", 0) == 0
    assert faults["collectives"]["all_gather"] == 1
    assert faults["collectives"]["psum"] == plain["collectives"]["psum"]


# --------------------------------------------------------------------------
# CLI
# --------------------------------------------------------------------------

def _run_cli(args, env_extra=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.update(env_extra or {})
    return subprocess.run(
        [sys.executable, "-m",
         f"{contracts.PKG}.analysis"] + args,
        cwd=REPO, env=env, capture_output=True, text=True, timeout=300)


def test_cli_exit_zero_on_clean_tree():
    r = _run_cli(["--rules", "ast,audit"])
    assert r.returncode == 0, r.stdout + r.stderr


def test_cli_exit_one_on_planted_finding(tmp_path, monkeypatch, capsys):
    """Plant a forbidden host sync in a throwaway hot-path copy of the
    repo surface and check the CLI exits 1 (the CI gate behavior)."""
    plant = tmp_path / "scripts" / "profile_round.py"
    plant.parent.mkdir(parents=True)
    plant.write_text("def hot(metrics):\n    return float(metrics)\n")
    findings = ast_rules.scan([str(plant)], str(tmp_path))
    assert [f.rule for f in findings] == ["host-sync"]
    # the CLI maps findings -> exit 1 (in-process, scan_repo planted)
    from defending_against_backdoors_with_robust_learning_rate_tpu.analysis.__main__ import (
        main as cli_main)
    monkeypatch.setattr(ast_rules, "scan_repo", lambda root: findings)
    assert cli_main(["--rules", "ast"]) == 1
    assert "host-sync" in capsys.readouterr().out
    monkeypatch.setattr(ast_rules, "scan_repo", lambda root: [])
    assert cli_main(["--rules", "ast"]) == 0


def test_cli_json_clean_tree():
    r = _run_cli(["--rules", "ast,audit", "--json"])
    assert r.returncode == 0, r.stdout + r.stderr
    assert json.loads(r.stdout) == []


def test_cli_rejects_unknown_rules():
    r = _run_cli(["--rules", "nope"])
    assert r.returncode == 2


def test_async_budgets_and_baseline_pins():
    """ISSUE-12 acceptance: the buffered-async families keep each mode's
    pinned plan — avg+RLR within the 2L+2 psum budget (measured 2L+1:
    the packed count/weight/loss lane replaces the weight psum + loss
    pmean), faults + the staleness-stacked pending shape still exactly one
    [m]-bit validation all_gather — and the counts are topology-free
    (the @16w pod-shape records land via scripts/check_static.py)."""
    specs = contracts.check_specs()
    findings, rec = jaxpr_lint.check_family(specs["sharded_rlr_avg_async"])
    assert findings == []
    assert rec["collectives"] == {"psum": 17}   # 2L+1 on the 8-leaf CNN

    path = jaxpr_lint.baseline_path(REPO)
    with open(path) as f:
        pinned = json.load(f)["families"]
    for key in ("vmap_rlr_avg_async",
                "sharded_rlr_avg_async", "sharded_rlr_avg_async@16w",
                "sharded_rlr_sign_async", "sharded_rlr_avg_async_stale",
                "sharded_rlr_avg_async_faults",
                "sharded_chained_rlr_avg_async",
                "sharded_rlr_avg_cohort_async"):
        assert key in pinned, f"{key} missing from analysis_baseline.json"
    # the vmap families stay collective-free; counts are topology-free
    assert pinned["vmap_rlr_avg_async"]["collectives"] == {}
    assert pinned["sharded_rlr_avg_async@16w"]["collectives"] == \
        pinned["sharded_rlr_avg_async"]["collectives"] == {"psum": 17}
    assert pinned["sharded_rlr_sign_async"]["collectives"] == {"psum": 9}
    # stale (pending-ladder shapes) + faults: exactly one all_gather each
    for key in ("sharded_rlr_avg_async_stale",
                "sharded_rlr_avg_async_faults"):
        assert pinned[key]["collectives"] == {"all_gather": 1,
                                              "psum": 17}, key

# --------------------------------------------------------------------------
# thread rules (host-concurrency races): synthetic snippets + clean gate
# --------------------------------------------------------------------------

def _scan_threads(tmp_path, source, relpath="scripts/drain_demo.py"):
    path = tmp_path / relpath
    path.parent.mkdir(parents=True, exist_ok=True)
    path.write_text(textwrap.dedent(source))
    return thread_rules.scan([str(path)], str(tmp_path))


def test_cross_thread_write_trips_and_locked_twin(tmp_path):
    bad = """
    import threading

    class Drain:
        def __init__(self):
            self._lock = threading.Lock()
            self._rows = []
            self._t = threading.Thread(target=self._worker)
            self._t.start()

        def _worker(self):
            self._rows = []          # unlocked write on the worker

        def push(self, row):
            with self._lock:
                self._rows.append(row)
    """
    f = _scan_threads(tmp_path, bad)
    assert _rules(f) == ["cross-thread-state"]
    assert any("_rows" in x.message for x in f)

    clean = bad.replace(
        "            self._rows = []          # unlocked write on the worker",
        "            with self._lock:\n"
        "                self._rows = []")
    assert _scan_threads(tmp_path, clean) == []


def test_cross_thread_write_pragma_suppression(tmp_path):
    src = """
    import threading

    class Drain:
        def __init__(self):
            self._lock = threading.Lock()
            self._rows = []
            threading.Thread(target=self._worker).start()

        def _worker(self):
            # static: ok(cross-thread-state)
            self._rows = []

        def push(self, row):
            with self._lock:
                self._rows.append(row)
    """
    assert _scan_threads(tmp_path, src) == []


def test_racy_file_write_trips_and_atomic_twin(tmp_path):
    bad = """
    import threading

    def _worker(path):
        with open(path, "w") as f:
            f.write("x")

    def start(path):
        threading.Thread(target=_worker, args=(path,)).start()
    """
    assert _rules(_scan_threads(tmp_path, bad)) == ["racy-file-write"]

    clean = """
    import os
    import threading

    def _worker(path):
        tmp = path + ".tmp"
        with open(tmp, "w") as f:
            f.write("x")
        os.replace(tmp, path)

    def start(path):
        threading.Thread(target=_worker, args=(path,)).start()
    """
    assert _scan_threads(tmp_path, clean) == []


def test_check_then_act_trips_and_guarded_twin(tmp_path):
    bad = """
    import os
    import threading

    def _worker(path):
        if os.path.exists(path):
            os.remove(path)

    def start(path):
        threading.Thread(target=_worker, args=(path,)).start()
    """
    f = _scan_threads(tmp_path, bad)
    assert _rules(f) == ["check-then-act"]

    clean = """
    import os
    import threading

    def _worker(path):
        if os.path.exists(path):
            try:
                os.remove(path)
            except OSError:
                pass        # another worker won the window

    def start(path):
        threading.Thread(target=_worker, args=(path,)).start()
    """
    assert _scan_threads(tmp_path, clean) == []


def test_repo_thread_scan_is_clean():
    """Satellite contract: every race finding on the tree is fixed or
    carries a written serialization argument (contracts.ALLOW)."""
    findings = thread_rules.scan_repo(REPO)
    assert findings == [], "\n".join(str(f) for f in findings)


# --------------------------------------------------------------------------
# coverage (program-family lattice): synthetic lattices + clean gate
# --------------------------------------------------------------------------

def _cov_spec(name, family, sharded=False):
    return contracts.CheckSpec(name=name, family=family, sharded=sharded,
                               cfg_overrides={}, collective_budget={})


def _cov_kwargs(**over):
    """A minimal synthetic lattice that audits clean; each test perturbs
    exactly one input."""
    base = dict(
        tokens=["_async"],
        drivers={"_async": {"agg_mode": "buffered"}},
        reachable={"round": ["dense"], "chained": ["dense+chain"]},
        specs={"pin_round": _cov_spec("pin_round", "round"),
               "pin_chained": _cov_spec("pin_chained", "chained")},
        baseline={"families": {"pin_round": {}, "pin_chained": {}}},
        donated=("chained",),
        waived={},
        program_fields=set(),
        run_fields=set(),
        exempt={},
        topologies=(contracts.REFERENCE_TOPOLOGY,),
    )
    base.update(over)
    return base


def test_coverage_synthetic_lattice_is_clean():
    assert coverage.audit(REPO, **_cov_kwargs()) == []


def test_coverage_missing_pin_for_reachable_family():
    kw = _cov_kwargs(reachable={"round": ["dense"],
                                "round_async": ["dense+_async"],
                                "chained": ["dense+chain"]})
    f = coverage.audit(REPO, **kw)
    assert _rules(f) == ["missing-pin"]
    assert "round_async" in f[0].message
    # a waiver with a written reason covers it...
    kw["waived"] = {"round_async": "no mesh: collective-free twin"}
    assert coverage.audit(REPO, **kw) == []
    # ...but an empty reason does not
    kw["waived"] = {"round_async": "  "}
    assert _rules(coverage.audit(REPO, **kw)) == ["missing-pin"]


def test_coverage_stale_waiver():
    kw = _cov_kwargs(waived={"ghost": "never emitted"})
    f = coverage.audit(REPO, **kw)
    assert _rules(f) == ["stale-waiver"] and "ghost" in f[0].message
    kw = _cov_kwargs(waived={"round": "already has a spec"})
    assert _rules(coverage.audit(REPO, **kw)) == ["stale-waiver"]


def test_coverage_dead_spec():
    kw = _cov_kwargs()
    kw["specs"] = dict(kw["specs"],
                       pin_ghost=_cov_spec("pin_ghost", "ghost"))
    f = coverage.audit(REPO, **kw)
    rules = _rules(f)
    assert "dead-spec" in rules and "topology-gap" in rules
    assert any("pin_ghost" in x.message for x in f)


def test_coverage_dead_baseline_record():
    kw = _cov_kwargs()
    kw["baseline"] = {"families": dict(kw["baseline"]["families"],
                                       zzz_removed_spec={})}
    f = coverage.audit(REPO, **kw)
    assert _rules(f) == ["dead-baseline"]
    assert "zzz_removed_spec" in f[0].message


def test_coverage_donated_drift_both_directions():
    f = coverage.audit(REPO, **_cov_kwargs(donated=()))
    assert _rules(f) == ["donated-drift"] and "chained" in f[0].message
    f = coverage.audit(REPO, **_cov_kwargs(donated=("chained", "ghost")))
    assert _rules(f) == ["donated-drift"] and "ghost" in f[0].message


def test_coverage_run_name_blind_field():
    kw = _cov_kwargs(program_fields={"bs", "arch"},
                     run_fields={"arch"})
    f = coverage.audit(REPO, **kw)
    assert _rules(f) == ["run-name-blind"] and "'bs'" in f[0].message
    # an exemption with a reason covers it; stale exemptions are flagged
    kw["exempt"] = {"bs": "reference vocabulary separates by log_dir"}
    assert coverage.audit(REPO, **kw) == []
    kw["exempt"] = {"bs": "reason", "arch": "but run_name reads arch"}
    f = coverage.audit(REPO, **kw)
    assert _rules(f) == ["stale-run-name-exemption"]


def test_coverage_new_suffix_branch_fails_loudly(tmp_path):
    """ISSUE-19 acceptance: a new family_suffix branch without a
    SUFFIX_DRIVERS mapping (so without CheckSpecs either) must fail —
    the lattice walk cannot enumerate the new slice silently."""
    cc = tmp_path / contracts.PKG / "utils" / "compile_cache.py"
    cc.parent.mkdir(parents=True)
    cc.write_text(textwrap.dedent("""
        def family_suffix(cfg):
            sfx = "_async" if is_buffered(cfg) else ""
            if getattr(cfg, "zigzag", 0):
                sfx += "_zz"
            return sfx
        """))
    tokens = coverage.suffix_tokens(str(tmp_path))
    assert tokens == ["_async", "_zz"]
    f = coverage.audit(REPO, **_cov_kwargs(tokens=tokens))
    assert _rules(f) == ["suffix-unmapped"] and "_zz" in f[0].message
    # the reverse direction: a driver for a token the algebra dropped
    kw = _cov_kwargs(drivers={"_async": {"agg_mode": "buffered"},
                              "_gone": {"tenants": 9}})
    f = coverage.audit(REPO, **kw)
    assert _rules(f) == ["suffix-unmapped"] and "_gone" in f[0].message


def test_suffix_tokens_match_driver_table():
    tokens = coverage.suffix_tokens(REPO)
    assert tokens == ["_async", "_mt"]
    assert set(tokens) == set(contracts.SUFFIX_DRIVERS)


def test_run_name_walk_sees_getattr_and_new_fields():
    """run_name reads agg_mode through a getattr helper (is_buffered) —
    the walker must see through it; the four fields the coverage pass
    surfaced as collision bugs must now mark the run dir."""
    fields = coverage.run_name_fields(REPO)
    for f in ("agg_mode", "corrupt_mode",
              "straggler_epochs", "traffic_latency_sigma", "quarantine"):
        assert f in fields, f


def test_repo_coverage_scan_is_clean():
    """Satellite contract: the reachable lattice is exactly covered —
    every family pinned or waived with a reason, baseline exactly the
    live spec x topology matrix, donated set drift-free, every
    program-provenance field in run_name or exempted with a reason."""
    findings = coverage.scan_repo(REPO)
    assert findings == [], "\n".join(str(f) for f in findings)


def test_coverage_deleted_spec_fails_loudly():
    """ISSUE-19 acceptance: deleting a CheckSpec whose family has no
    waiver makes the gate fail (missing-pin) and orphans its committed
    baseline records (dead-baseline)."""
    specs = dict(contracts.check_specs())
    del specs["sharded_rlr_avg_diag"]
    f = coverage.audit(REPO, specs=specs)
    assert any(x.rule == "missing-pin" and "round_sharded_diag"
               in x.message for x in f)
    assert any(x.rule == "dead-baseline" and "sharded_rlr_avg_diag"
               in x.message for x in f)


def test_write_baseline_prunes_dead_records(tmp_path):
    live = sorted(coverage.live_baseline_keys(REPO))[0]
    path = tmp_path / "analysis_baseline.json"
    path.write_text(json.dumps({"families": {
        live: {"collectives": {}}, "zzz_dead": {"collectives": {}}}}))
    # legacy merge keeps unknown records; the prune path drops them
    jaxpr_lint.write_baseline(str(tmp_path), {"families": {}})
    fams = json.loads(path.read_text())["families"]
    assert "zzz_dead" in fams
    jaxpr_lint.write_baseline(str(tmp_path), {"families": {}}, prune=True)
    fams = json.loads(path.read_text())["families"]
    assert live in fams and "zzz_dead" not in fams


def test_cli_staged_exit_codes_and_census(monkeypatch, tmp_path):
    """Exit codes are staged per pass tier (1 legacy, 3 thread,
    4 coverage) and the census JSON records both."""
    from defending_against_backdoors_with_robust_learning_rate_tpu.analysis.__main__ import (
        main as cli_main)
    planted = [ast_rules.Finding("cross-thread-state", "x.py", 1, "p")]
    monkeypatch.setattr(thread_rules, "scan_repo", lambda root: planted)
    monkeypatch.setattr(coverage, "scan_repo", lambda root: [])
    assert cli_main(["--rules", "thread,coverage"]) == 3
    monkeypatch.setattr(thread_rules, "scan_repo", lambda root: [])
    monkeypatch.setattr(coverage, "scan_repo", lambda root: planted)
    census = tmp_path / "census.json"
    assert cli_main(["--rules", "thread,coverage",
                     "--census-json", str(census)]) == 4
    doc = json.loads(census.read_text())
    assert doc == {"census": {"thread": 0, "coverage": 1},
                   "exit_code": 4}
    # legacy findings outrank the newer tiers
    monkeypatch.setattr(ast_rules, "scan_repo", lambda root: planted)
    assert cli_main(["--rules", "ast,thread,coverage"]) == 1
    monkeypatch.setattr(ast_rules, "scan_repo", lambda root: [])
    monkeypatch.setattr(coverage, "scan_repo", lambda root: [])
    assert cli_main(["--rules", "thread,coverage"]) == 0
