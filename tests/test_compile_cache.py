"""Compile-persistence & AOT executable bank (utils/compile_cache.py).

Covers: executable serialize/deserialize round-trip (executed, on a host
with more devices than the program uses), manifest invalidation on a
changed config fingerprint or an edited source file, cache-root
resolution, the program-family planner, and the precompile -> train
warm-start handoff (a banked family is LOADED, not recompiled, by a
subsequent train.run)."""

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from defending_against_backdoors_with_robust_learning_rate_tpu.config import Config
from defending_against_backdoors_with_robust_learning_rate_tpu.utils import (
    compile_cache as cc)

TINY = Config(data="synthetic", num_agents=4, bs=32, local_ep=1,
              synth_train_size=256, synth_val_size=64, eval_bs=64,
              rounds=4, snap=2, seed=3, tensorboard=False)


def _example():
    return (jax.ShapeDtypeStruct((8, 8), jnp.float32),)


def test_fingerprint_stability_and_invalidation():
    fp = cc.fingerprint(TINY, "round", _example())
    assert fp == cc.fingerprint(TINY, "round", _example())
    # program-shaping fields invalidate
    assert fp != cc.fingerprint(TINY.replace(bs=64), "round", _example())
    assert fp != cc.fingerprint(TINY.replace(aggr="sign"), "round",
                                _example())
    # family and arg shapes are part of the key
    assert fp != cc.fingerprint(TINY, "chained", _example())
    assert fp != cc.fingerprint(
        TINY, "round", (jax.ShapeDtypeStruct((4, 8), jnp.float32),))
    # pure IO/driver knobs do not (seed/chain/snap/log_dir are excluded)
    for kw in ({"seed": 9}, {"chain": 7}, {"snap": 5},
               {"log_dir": "/elsewhere"}, {"rounds": 999},
               {"async_metrics": False}, {"compile_cache_dir": "/x"}):
        assert fp == cc.fingerprint(TINY.replace(**kw), "round", _example())
    # diagnostics normalizes OFF for non-diag families, stays for _diag
    assert fp == cc.fingerprint(TINY.replace(diagnostics=True), "round",
                                _example())
    assert (cc.fingerprint(TINY, "round_diag", _example())
            != cc.fingerprint(TINY.replace(diagnostics=True), "round_diag",
                              _example()))


def test_bank_roundtrip_and_manifest_invalidation(tmp_path, monkeypatch):
    """Cold compile banks a loadable executable; a fresh bank instance
    loads AND EXECUTES it (disk round-trip, no XLA); a changed config
    fingerprint or an edited package source file misses and recompiles."""
    # the habitat of the execution_devices default: a single-device
    # executable reloaded where the backend has more devices (the faked
    # 8-device harness here, any four-chip host in production) must stay
    # pinned to the device it was compiled for
    assert jax.device_count() > 1
    bank = cc.AotBank(str(tmp_path))
    jit_obj = jax.jit(lambda x: x @ x.T + 1.0)
    x = jnp.arange(64, dtype=jnp.float32).reshape(8, 8)
    ex = cc.abstractify((x,))

    compiled, hit, secs, entry = bank.get_or_compile("unit", TINY, jit_obj,
                                                     ex)
    assert not hit and entry["compile_s"] >= 0
    want = np.asarray(jit_obj(x))
    np.testing.assert_array_equal(np.asarray(compiled(x)), want)
    names = os.listdir(bank.dir)
    assert any(n.endswith(".jex") for n in names)
    assert any(n.endswith(".json") for n in names)

    # fresh bank object = the next process: must LOAD, not recompile
    bank2 = cc.AotBank(str(tmp_path))
    loaded, hit2, _, entry2 = bank2.get_or_compile("unit", TINY, jit_obj, ex)
    assert hit2 and entry2["fingerprint"] == entry["fingerprint"]
    np.testing.assert_array_equal(np.asarray(loaded(x)), want)
    assert [e["family"] for e in bank2.entries()] == ["unit"]

    # changed config fingerprint => recompile (manifest invalidation)
    _, hit3, _, entry3 = bank2.get_or_compile("unit", TINY.replace(bs=64),
                                              jit_obj, ex)
    assert not hit3 and entry3["fingerprint"] != entry["fingerprint"]
    assert len(bank2.entries()) == 2

    # an edited source file => the warm run recompiles: an entry is only
    # ever served to the code that built it
    monkeypatch.setattr(cc, "source_digest", lambda: "edited")
    _, hit4, _, entry4 = bank2.get_or_compile("unit", TINY, jit_obj, ex)
    assert not hit4 and entry4["fingerprint"] != entry["fingerprint"]
    assert len(bank2.entries()) == 3


def test_source_digest_tracks_py_files_on_disk(tmp_path):
    pkg = tmp_path / "pkg"
    (pkg / "sub").mkdir(parents=True)
    (pkg / "a.py").write_text("x = 1\n")
    (pkg / "sub" / "b.py").write_text("y = 1\n")
    (pkg / "notes.txt").write_text("not code\n")

    def digest():
        cc.source_digest.cache_clear()
        return cc.source_digest(str(pkg))

    d0 = digest()
    assert d0 == digest()
    (pkg / "notes.txt").write_text("still not code\n")
    assert digest() == d0
    (pkg / "sub" / "b.py").write_text("y = 2\n")
    d1 = digest()
    assert d1 != d0
    (pkg / "sub" / "b.py").rename(pkg / "sub" / "c.py")
    assert digest() not in (d0, d1)
    cc.source_digest.cache_clear()
    # the real package digest is memoized per process and keys the
    # fingerprint (see test_bank_roundtrip_and_manifest_invalidation)
    assert cc.source_digest() == cc.source_digest()


def test_cache_root_resolution(tmp_path, monkeypatch):
    """$JAX_COMPILATION_CACHE_DIR > --compile_cache_dir > the fixed,
    git-ignored directory of the checkout. Under the variable XLA's cache
    is that directory exactly and this module never re-sets it in code."""
    machine, flag = str(tmp_path / "machine"), str(tmp_path / "flag")
    cfg = TINY.replace(compile_cache_dir=flag)
    monkeypatch.setenv(cc.CACHE_DIR_ENV, machine)
    assert cc.cache_root(cfg) == cc.cache_root(TINY) == machine
    updated = []
    real_update = jax.config.update
    monkeypatch.setattr(
        jax.config, "update",
        lambda name, val: (updated.append(name), real_update(name, val)))
    assert cc.enable_persistent_cache(cfg) == machine
    bank = cc.setup(cfg)
    assert bank.dir == os.path.join(machine, "aot")
    assert "jax_compilation_cache_dir" not in updated

    monkeypatch.delenv(cc.CACHE_DIR_ENV)
    assert cc.cache_root(cfg) == flag
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    assert cc.cache_root(TINY) == cc.CHECKOUT_CACHE_ROOT \
        == os.path.join(repo, ".compile_cache")
    with open(os.path.join(repo, ".gitignore")) as f:
        assert ".compile_cache/" in f.read().split()


def test_flag_root_persistent_cache_smoke(tmp_path, own_cache_root):
    """Without the variable, enable_persistent_cache points jax at
    <root>/xla and compiles land there as cache entries."""
    xla_dir = cc.enable_persistent_cache(
        TINY.replace(compile_cache_dir=str(tmp_path)))
    assert xla_dir == os.path.join(str(tmp_path), "xla")
    assert jax.config.jax_compilation_cache_dir == xla_dir
    f = jax.jit(lambda x: jnp.sin(x) @ jnp.cos(x.T))
    jax.block_until_ready(f(jnp.ones((16, 16))))
    assert any(n.endswith("-cache") for n in os.listdir(xla_dir))


def _plan(cfg, host_mode=None):
    from defending_against_backdoors_with_robust_learning_rate_tpu.data.registry import (
        get_federated_data)
    from defending_against_backdoors_with_robust_learning_rate_tpu.fl.common import (
        make_normalizer)
    from defending_against_backdoors_with_robust_learning_rate_tpu.models.registry import (
        get_model)

    fed = get_federated_data(cfg)
    model = get_model(cfg.data, cfg.model_arch, cfg.dtype)
    norm = make_normalizer(fed.mean, fed.std, fed.raw_is_normalized)
    return cc.plan_programs(cfg, model, norm, fed, host_mode=host_mode)


def _plan_families(cfg, host_mode=None):
    return [s.family for s in _plan(cfg, host_mode)]


def test_plan_programs_families():
    # device-resident, chained: the flagship bench family set
    assert _plan_families(TINY.replace(chain=2)) == [
        "round", "chained", "eval_val", "eval_poison"]
    # unchained (chain budget 1): no chained family
    assert _plan_families(TINY) == ["round", "eval_val", "eval_poison"]
    # diagnostics adds the diag variant
    assert _plan_families(TINY.replace(diagnostics=True)) == [
        "round", "round_diag", "eval_val", "eval_poison"]
    # host-sampled mode swaps in the host families
    assert _plan_families(TINY.replace(chain=2), host_mode=True) == [
        "round_host", "chained_host", "eval_val", "eval_poison"]
    # faults disable host chaining (per-round corrupt flags ride each
    # dispatch — mirrors the driver)
    assert _plan_families(TINY.replace(chain=2, dropout_rate=0.3),
                          host_mode=True) == [
        "round_host", "eval_val", "eval_poison"]


def test_chained_families_donate_params():
    """Donation-audit pin (contracts.DONATED_FAMILIES): every chained
    family must donate its params argument — the lowered StableHLO
    carries the input-output alias on arg 0, so no parameter copy rides a
    dispatched block. The per-round families deliberately keep params
    alive (diagnostics prev_params, parity callers, supervised retry) —
    pinned un-aliased here so the asymmetry is a contract, not an
    accident."""
    from defending_against_backdoors_with_robust_learning_rate_tpu.analysis.contracts import (
        DONATED_FAMILIES)
    cfg = TINY.replace(bs=16, chain=2, robustLR_threshold=3)
    seen = set()
    for lcfg, host_mode in ((cfg, None), (cfg, True),
                            (cfg.replace(agg_mode="buffered"), None)):
        for spec in _plan(lcfg, host_mode):
            if not spec.family.startswith(("round", "chained")):
                continue
            text = cc.lower_program(spec.jit_obj,
                                    spec.example_args).as_text()
            donated = "tf.aliasing_output" in text
            if spec.family in DONATED_FAMILIES:
                assert donated, f"{spec.family} must donate params"
                seen.add(spec.family)
            else:
                assert not donated, \
                    f"{spec.family} must NOT donate (prev_params/retry)"
    assert seen == {"chained", "chained_host", "chained_async"}


def test_precompile_then_train_loads(tmp_path, capsys, own_cache_root):
    """Acceptance: a precompiled family is LOADED (not recompiled) by the
    subsequent train.run, and the warm run's results equal a cold run's."""
    from defending_against_backdoors_with_robust_learning_rate_tpu import train
    from defending_against_backdoors_with_robust_learning_rate_tpu.data.registry import (
        get_federated_data)
    from defending_against_backdoors_with_robust_learning_rate_tpu.fl.common import (
        make_normalizer)
    from defending_against_backdoors_with_robust_learning_rate_tpu.models.registry import (
        get_model)
    from defending_against_backdoors_with_robust_learning_rate_tpu.utils.metrics import (
        NullWriter)

    cfg = TINY.replace(compile_cache_dir=str(tmp_path),
                       log_dir=str(tmp_path / "logs"))
    fed = get_federated_data(cfg)
    model = get_model(cfg.data, cfg.model_arch, cfg.dtype)
    norm = make_normalizer(fed.mean, fed.std, fed.raw_is_normalized)
    bank = cc.AotBank(str(tmp_path))
    rows = cc.precompile(cfg, model, norm, fed, bank, log=lambda m: None)
    assert {r["family"] for r in rows} == {"round", "eval_val",
                                           "eval_poison"}
    assert not any(r["cache_hit"] for r in rows)

    summary = train.run(cfg, writer=NullWriter())
    out = capsys.readouterr().out
    assert "[aot] round: loaded from cache" in out
    assert "[aot] eval_val: loaded from cache" in out
    assert "compiled+banked" not in out   # nothing recompiled
    assert summary["round"] == cfg.rounds

    # and the warm executables compute the same training as a cache-free run
    ref = train.run(cfg.replace(compile_cache=False), writer=NullWriter())
    assert summary["val_acc"] == ref["val_acc"]
    assert summary["val_loss"] == ref["val_loss"]
    assert summary["poison_acc"] == ref["poison_acc"]


@pytest.mark.slow  # two in-process bench.main runs (~4 min on the CI box)
def test_bench_cold_then_warm_cache_hit(tmp_path, monkeypatch, capsys,
                                        own_cache_root):
    """bench.py acceptance: a second run on a populated cache reports
    cache_hit true and compile_s_warm <= 20% of compile_s_cold."""
    import json
    import bench

    argv = ["bench.py", "--platform", "cpu", "--chain", "2", "--blocks",
            "1", "--synth_train_size", "2560", "--compile_cache_dir",
            str(tmp_path)]

    def run_once():
        monkeypatch.setattr("sys.argv", argv)
        bench.main()
        out = [l for l in capsys.readouterr().out.splitlines()
               if l.startswith("{")]
        return json.loads(out[-1])

    cold = run_once()
    assert cold["cache_hit"] is False and cold["compile_s_cold"] > 0
    warm = run_once()
    assert warm["cache_hit"] is True
    assert warm["compile_s_warm"] <= 0.2 * warm["compile_s_cold"]
    assert warm["host_sync"]["eval_sync_s"] >= warm["host_sync"][
        "eval_dispatch_s"]
