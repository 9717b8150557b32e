"""Calibrated dispatch (core.calibrate): fit/cache round-trip, versioned
invalidation, heuristic-fallback parity, the policy consult points in
hybrid/streaming/batcher, and the fig7 regret regression pins.

The load-bearing claims:

* with no table the policy reproduces the Eq. 2 heuristic *exactly* —
  installing calibration changes wall clock only, never results;
* a cached table steers dispatch only when schema, code version, and
  device fingerprint all match — anything stale degrades to the
  heuristic instead of dispatching on foreign timings;
* the kernel probe is cached per process and its fallback tallied once,
  not once per dispatch (the fig7 N3_M512/N5_M512 regret root cause).
"""

import json

import numpy as np
import pytest

from repro.core import EpisodeBatch, EventStream, calibrate, hybrid
from repro.core.calibrate import (CalibrationTable, DispatchPolicy,
                                  FEATURE_NAMES, GridSpec, analytic_seconds,
                                  features, fit_table, install_table,
                                  load_table)
from repro.obs import REGISTRY

HW = {"peak_flops": 197e12, "hbm_bw": 819e9, "ici_bw": 50e9}


@pytest.fixture(autouse=True)
def _hermetic_policy():
    """Every test starts (and leaves) the process on the heuristic."""
    calibrate.clear_policy()
    REGISTRY.clear_family("dispatch_policy_total")
    yield
    calibrate.clear_policy()
    REGISTRY.clear_family("dispatch_policy_total")


def small_stream(n=64, num_types=5, seed=0):
    rng = np.random.default_rng(seed)
    return EventStream(
        types=rng.integers(0, num_types, size=n).astype(np.int32),
        times=np.cumsum(rng.integers(1, 3, size=n)).astype(np.int32),
        num_types=num_types)


def small_eps(m=4, n=3, num_types=5, seed=1):
    rng = np.random.default_rng(seed)
    et = rng.integers(0, num_types, size=(m, n)).astype(np.int32)
    tlo = np.full((m, n - 1), 1, np.int32)
    thi = np.full((m, n - 1), 8, np.int32)
    return EpisodeBatch(et, tlo, thi)


def synth_points(true, n_events=(1024, 4096), seed=0):
    """Grid timings generated from known per-engine linear models."""
    rng = np.random.default_rng(seed)
    pts = []
    for engine, coef in true.items():
        for n_ep in (2, 3, 5):
            for m in (16, 128, 512):
                for n_ev in n_events:
                    for q in ((1,) if engine == "ptpe" else (1, 4, 8)):
                        a = analytic_seconds(engine, n_ep, m, n_ev, q,
                                             1, HW)
                        phi = features(n_ep, m, n_ev, q, a)
                        y = sum(c * x for c, x in zip(coef, phi))
                        y *= 1.0 + rng.uniform(-0.01, 0.01)
                        pts.append({"engine": engine, "n_episode": n_ep,
                                    "m": m, "n_events": n_ev, "q": q,
                                    "devices": 1, "seconds": y})
    return pts


def make_table(true, device_kind="cpu:cpux1"):
    return fit_table(synth_points(true), HW, device_kind=device_kind)


# ptpe flat-ish; mapconcatenate cheap on events but scales with cells —
# so low M prefers mapc, high M prefers ptpe (the fig7 shape)
TRUE = {
    "ptpe": [2e-3, 1e-3, 1e-4, 1e-5, 0.0, 0.0],
    "mapconcatenate": [1e-3, 2e-4, 5e-3, 1e-4, 2e-4, 0.0],
}


# ------------------------------------------------------------ model + fit


def test_analytic_seconds_engine_shape():
    kw = dict(n_episode=3, m=128, n_events=4096, q=8, devices=4, hw=HW)
    t = {e: analytic_seconds(e, kw["n_episode"], kw["m"], kw["n_events"],
                             kw["q"], kw["devices"], kw["hw"])
         for e in calibrate.ENGINES}
    # the kernel halves effective traffic; sharding divides it by devices
    assert t["mapconcat_kernel"] < t["mapconcatenate"]
    assert t["mapconcat_sharded"] < t["mapconcat_kernel"]
    assert t["ptpe"] < t["mapconcatenate"]
    with pytest.raises(ValueError):
        analytic_seconds("nope", 3, 128, 4096, 8, 1, HW)


def test_fit_recovers_relative_ordering():
    table = make_table(TRUE)
    assert set(table.coeffs) == set(TRUE)
    for engine, coef in TRUE.items():
        for (n_ep, m, n_ev) in ((2, 16, 1024), (5, 512, 4096)):
            a = analytic_seconds(engine, n_ep, m, n_ev, 1, 1, HW)
            truth = sum(c * x for c, x in
                        zip(coef, features(n_ep, m, n_ev, 1, a)))
            got = table.predict(engine, n_episode=n_ep, m=m,
                                n_events=n_ev, q=1)
            assert got == pytest.approx(truth, rel=0.15)


def test_predict_unmeasured_engine_is_none():
    table = make_table(TRUE)
    assert table.predict("mapconcat_kernel", n_episode=3, m=16,
                         n_events=1024) is None


# ----------------------------------------------- cache + invalidation


def test_table_roundtrip(tmp_path):
    table = make_table(TRUE)
    path = str(tmp_path / "cal" / "t.json")
    table.save(path)
    back = load_table(path)
    assert back is not None
    assert back.device_kind == table.device_kind
    assert back.coeffs == table.coeffs
    assert back.segment_counts == table.segment_counts


@pytest.mark.parametrize("corrupt", [
    lambda d: d.update(schema=99),
    lambda d: d.update(code_version="cal0-ancient"),
    lambda d: d["coeffs"].update(ptpe=[1.0, 2.0]),  # wrong feature dim
    lambda d: d.pop("device_kind"),
])
def test_stale_table_is_rejected(tmp_path, corrupt):
    doc = make_table(TRUE).to_doc()
    corrupt(doc)
    path = tmp_path / "t.json"
    path.write_text(json.dumps(doc))
    assert load_table(str(path)) is None


def test_load_missing_or_garbage(tmp_path):
    assert load_table(str(tmp_path / "absent.json")) is None
    bad = tmp_path / "bad.json"
    bad.write_text("{not json")
    assert load_table(str(bad)) is None


def test_install_wrong_device_degrades_to_heuristic(tmp_path):
    table = make_table(TRUE, device_kind="tpu:TPU v5ex8")
    path = str(tmp_path / "t.json")
    table.save(path)
    pol = install_table(path)
    assert pol.table is None and pol.source == "heuristic"
    # ... and without the match requirement it steers
    pol = install_table(path, require_device_match=False)
    assert pol.table is not None and pol.source == "calibrated"


def test_env_table_opt_in(tmp_path, monkeypatch):
    table = make_table(TRUE, device_kind=calibrate.device_fingerprint())
    path = str(tmp_path / "t.json")
    table.save(path)
    monkeypatch.setenv(calibrate.ENV_TABLE, path)
    calibrate.clear_policy()
    assert calibrate.get_policy().source == "calibrated"
    monkeypatch.setenv(calibrate.ENV_TABLE, str(tmp_path / "absent.json"))
    calibrate.clear_policy()
    assert calibrate.get_policy().source == "heuristic"


def test_default_table_path_is_fingerprint_scoped(monkeypatch):
    monkeypatch.setenv(calibrate.ENV_TABLE_DIR, "/cal")
    p = calibrate.default_table_path()
    assert p.startswith("/cal/") and p.endswith(".json")
    assert "/" not in p[len("/cal/"):]


# ------------------------------------------------------------- policy


def test_heuristic_parity_with_eq2(monkeypatch):
    """No table: choose() must reproduce hybrid's Eq. 2 exactly."""
    pol = DispatchPolicy()
    monkeypatch.setattr(hybrid, "crossover", lambda n: 100)
    # above crossover, no kernel -> ptpe
    c = pol.choose(n_events=4096, n_episode=3, m=512, kernel_ok=False)
    assert (c.engine, c.source) == ("ptpe", "heuristic")
    # below crossover, no kernel -> mapconcatenate
    c = pol.choose(n_events=4096, n_episode=3, m=16, kernel_ok=False)
    assert c.engine == "mapconcatenate"
    # long stream + small batch + kernel -> the segmented kernel
    c = pol.choose(n_events=4096, n_episode=3, m=16, kernel_ok=True)
    assert c.engine == "mapconcat_kernel"
    # ... upgraded to the sharded form on a multi-device mesh
    c = pol.choose(n_events=4096, n_episode=3, m=16, kernel_ok=True,
                   shard_devices=4)
    assert c.engine == "mapconcat_sharded"
    # short stream never takes the kernel
    c = pol.choose(n_events=512, n_episode=3, m=16, kernel_ok=True)
    assert c.engine == "mapconcatenate"


def test_regression_fig7_many_episode_rows_pin_ptpe():
    """The fig7 N3_M512/N5_M512 2x-regret pin: on a single-device host
    with no kernel the heuristic must hand M=512 to PTPE."""
    pol = DispatchPolicy()
    for n in (3, 5):
        c = pol.choose(n_events=20000, n_episode=n, m=512,
                       kernel_ok=False, shard_devices=1)
        assert c.engine == "ptpe", f"N{n}_M512 regressed to {c.engine}"


def test_calibrated_choice_is_argmin_and_cached():
    pol = DispatchPolicy(make_table(TRUE))
    lo = pol.choose(n_events=4096, n_episode=3, m=16, kernel_ok=False)
    hi = pol.choose(n_events=4096, n_episode=3, m=512, kernel_ok=False)
    assert lo.source == hi.source == "calibrated"
    assert lo.engine == "mapconcatenate"
    assert hi.engine == "ptpe"
    assert hi.predicted_s == pol.table.predict(
        "ptpe", n_episode=3, m=512, n_events=4096, q=1)
    # same shape -> cached decision object, and n is bucketed
    assert pol.choose(n_events=4000, n_episode=3, m=512,
                      kernel_ok=False) is hi


def test_calibrated_never_picks_unavailable_engine():
    pol = DispatchPolicy(make_table(TRUE))
    for m in (16, 128, 512):
        c = pol.choose(n_events=4096, n_episode=3, m=m, kernel_ok=False)
        assert c.engine in ("ptpe", "mapconcatenate")


def test_choose_stream_matches_regimes(monkeypatch):
    pol = DispatchPolicy(make_table(TRUE))
    assert pol.choose_stream(n_episode=3, m=512).engine == "ptpe"
    assert pol.choose_stream(n_episode=3, m=16).engine == "mapconcatenate"
    # heuristic branch defers to Eq. 2
    monkeypatch.setattr(hybrid, "crossover", lambda n: 100)
    heur = DispatchPolicy()
    assert heur.choose_stream(n_episode=3, m=512).engine == "ptpe"
    assert heur.choose_stream(n_episode=3, m=16).engine == "mapconcatenate"


def test_choose_segments_heuristic_keeps_caller_preference():
    pol = DispatchPolicy()
    q, src = pol.choose_segments([8, 4, 1], engine="mapconcatenate",
                                 n_episode=3, m=16, n_events=4096)
    assert (q, src) == (8, "heuristic")
    with pytest.raises(ValueError):
        pol.choose_segments([], engine="mapconcatenate", n_episode=3,
                            m=16, n_events=4096)


def test_choose_segments_calibrated_scores_candidates():
    pol = DispatchPolicy(make_table(TRUE))
    q, src = pol.choose_segments([8, 4, 1], engine="mapconcatenate",
                                 n_episode=3, m=16, n_events=4096)
    assert src == "calibrated"
    best = min((pol.table.predict("mapconcatenate", n_episode=3, m=16,
                                  n_events=4096, q=c), c)
               for c in (8, 4, 1))[1]
    assert q == best


def test_predict_single_none_under_heuristic():
    assert DispatchPolicy().predict_single(
        "ptpe", n_episode=3, m=16) is None
    got = DispatchPolicy(make_table(TRUE)).predict_single(
        "ptpe", n_episode=3, m=16)
    assert got is not None and got > 0


def test_decisions_exported_to_registry():
    pol = DispatchPolicy()
    for _ in range(3):
        pol.choose(n_events=4096, n_episode=3, m=512, kernel_ok=False)
    stats = pol.stats()
    assert stats["source"] == "heuristic"
    assert stats["decisions"] == {"ptpe/heuristic": 3}


# ------------------------------------------------- consult-point wiring


def test_hybrid_dispatch_bit_identical_across_policy(tmp_path):
    stream, eps = small_stream(), small_eps()
    ref = np.asarray(hybrid.count_dispatch(stream, eps, engine="ptpe"))
    got_heur = np.asarray(hybrid.count_dispatch(stream, eps,
                                                engine="hybrid"))
    # a table rigged so hybrid routes to mapconcatenate instead
    table = make_table({"ptpe": [1.0, 0, 0, 0, 0, 0],
                        "mapconcatenate": [1e-6, 0, 0, 0, 0, 0]})
    calibrate.set_policy(DispatchPolicy(table))
    got_cal = np.asarray(hybrid.count_dispatch(stream, eps,
                                               engine="hybrid"))
    np.testing.assert_array_equal(ref, got_heur)
    np.testing.assert_array_equal(ref, got_cal)
    dec = calibrate.policy_stats()["decisions"]
    assert dec.get("mapconcatenate/calibrated", 0) >= 1


def test_probe_cached_and_tallied_once():
    from repro.kernels.tally import fallback_counts
    hybrid._reset_probe_cache()
    REGISTRY.clear_family("kernel_calls")
    first = hybrid._mapc_kernel_available()
    for _ in range(5):
        assert hybrid._mapc_kernel_available() == first
    tallies = fallback_counts().get("hybrid_mapc_probe", 0)
    assert tallies == (0 if first else 1)
    hybrid._reset_probe_cache()


def test_crossover_capacity_and_kernel_aware(monkeypatch):
    monkeypatch.setattr(hybrid, "parallel_units", lambda: 1)
    monkeypatch.setattr(hybrid, "_mapc_kernel_available", lambda: False)
    assert hybrid.crossover(4) == 0
    # the segmented kernel gives a lone device one real segment axis
    monkeypatch.setattr(hybrid, "_mapc_kernel_available", lambda: True)
    assert hybrid.crossover(4) == int(hybrid.f_of_n(4))
    monkeypatch.setattr(hybrid, "parallel_units", lambda: 8)
    assert hybrid.crossover(2) > hybrid.crossover(8) > 0


def test_batcher_prior_decodes_seam_keys():
    from repro.service.batcher import _policy_prior
    assert _policy_prior(("a1", 16, 3, 4)) is None  # heuristic: no prior
    calibrate.set_policy(DispatchPolicy(make_table(TRUE)))
    one = _policy_prior(("a1", 16, 3, 4))
    assert one is not None and one > 0
    mapc = _policy_prior(("mapc", 16, 3, 8, 4))
    assert mapc is not None and mapc > 0
    # kernel-side seams carry shape tuples; unmeasured engine -> None;
    # the carried kernels launch unfused and never reach the gate
    assert _policy_prior(("a1k", 3, 4, False, (3, 16))) is None
    assert _policy_prior(("mapck", 3, 4, False, (3, 16), 8)) is None


def test_service_stats_surface_calibration():
    from repro.service import MiningService
    svc = MiningService()
    stats = svc.stats()
    assert stats["calibration"]["source"] in ("heuristic", "calibrated")
    assert "decisions" in stats["calibration"]


# ------------------------------------------- measurement path (smoke)


def test_measure_fit_install_roundtrip(tmp_path):
    spec = GridSpec(episode_sizes=(2,), episode_counts=(4,),
                    event_counts=(64,), segment_counts=(1,),
                    repeats=1, warmup=1, num_types=5)
    seen = []
    pts = calibrate.measure_grid(spec, progress=seen.append)
    assert {p["engine"] for p in pts} >= {"ptpe", "mapconcatenate"}
    assert len(seen) == len(pts)
    assert all(p["seconds"] > 0 for p in pts)
    # too few points per engine for a 6-feature fit -> engine dropped,
    # prediction honestly None rather than extrapolated garbage
    table = fit_table(pts, HW, device_kind="test:x1")
    assert table.predict("ptpe", n_episode=2, m=4, n_events=64) is None


def test_calibrate_and_save_installs_and_caches(tmp_path):
    spec = GridSpec(episode_sizes=(2, 3), episode_counts=(4, 8, 16),
                    event_counts=(64, 128), segment_counts=(1, 2),
                    repeats=1, warmup=1, num_types=5)
    path = str(tmp_path / "cal" / "table.json")
    table, got_path = calibrate.calibrate_and_save(
        spec, hw=HW, out_path=path)
    assert got_path == path
    assert calibrate.get_policy().source == "calibrated"
    back = load_table(path)
    assert back is not None and back.coeffs == table.coeffs
    # the cached table steers a fresh process the same way
    calibrate.clear_policy()
    pol = install_table(path)
    assert pol.source == "calibrated"


# --------------------------------------------------- analysis-plane tie


def test_vmem_pass_covers_calibration_grid():
    from repro.analysis.vmem import check_calibration_grid
    from repro.kernels.ops import MAX_SEG_BRICK_LW
    pts = GridSpec().points()
    findings, summary = check_calibration_grid(pts, MAX_SEG_BRICK_LW)
    assert findings == []
    assert summary["vmem_calibration_points"] == len(pts)
    assert 0 < summary["vmem_calibration_worst_lw"] <= MAX_SEG_BRICK_LW
    # a tightened admission bound turns the same grid red
    findings, _ = check_calibration_grid(pts, 256)
    assert findings and all(f.rule == "VM304" for f in findings)


def test_calibrate_module_imports_stay_stdlib():
    """The analysis plane reads tables without jax/numpy: the module-
    level import set must stay stdlib-only (heavy deps are lazy)."""
    import ast
    import repro.core.calibrate as mod
    tree = ast.parse(open(mod.__file__).read())
    top = set()
    for node in tree.body:
        if isinstance(node, ast.Import):
            top |= {a.name.split(".")[0] for a in node.names}
        elif isinstance(node, ast.ImportFrom) and node.level == 0:
            top.add((node.module or "").split(".")[0])
    assert "jax" not in top and "numpy" not in top
