"""The ``game`` family's own pieces: its generator, its work model, the
lane-by-lane L-BFGS of its reference, its readers on hand-made records, a
traced run, and whole runs with the timed path broken underneath. Cells are
picked by their configuration's ``family``; sizes are ``tiny/<cell>.json``'s.
"""

import json

import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import manifest, run
from benchmark.gen import game_user_song
from benchmark.readers import (lane_evals_per_iter, lockstep_waste,
                               module_busy_share, op_roofline,
                               span_value_share)
from benchmark.reference import game as reference
from benchmark.reference.lbfgs import lbfgs
from benchmark.reference.lbfgs_lanes import lbfgs_lanes
from benchmark.work import game as work

GAME_CELLS = sorted(w["name"] for w in manifest.benchmark()["workloads"]
                    if manifest.cell(w["name"])[2]["family"] == "game")
WL = dict(rows=3000, users=200, songs=80, key_skew=1.0)
CFG = dict(dim_fixed=32, dim_random=8)
PEAKS = {"flops_per_s": 100e12, "hbm_bytes_per_s": 1000e9}
WINDOW = {"window_span": "game.re.solve", "window_count": "re_solves"}


# --- the generator ---------------------------------------------------------
def test_generator_repeats_from_a_seed_and_is_skewed():
    big = 2**31 + 12345  # the driver's seeds pass 32 signed bits
    a = game_user_song.generate(big, WL, CFG)
    b = game_user_song.generate(big, WL, CFG)
    c = game_user_song.generate(big + 1, WL, CFG)
    assert a["shards"]["fixed"].shape == (3000, 32)
    assert a["shards"]["item"].shape == (3000, 8)
    for k in ("fixed", "item"):
        assert np.array_equal(a["shards"][k], b["shards"][k])
        assert not np.array_equal(a["shards"][k], c["shards"][k])
    assert np.array_equal(a["y"], b["y"])
    # Zipf 1 over 200 ranks: the head holds 1 / H_200 = 17% of the rows, and
    # most entities hold few
    rows = np.bincount(a["ids"]["userId"], minlength=200)
    assert 0.12 < rows[0] / 3000 < 0.22
    assert np.median(rows) < 10
    assert 0.2 < float(np.mean(a["y"])) < 0.8
    # users and songs are picked independently
    assert abs(np.corrcoef(a["ids"]["userId"], a["ids"]["songId"])[0, 1]) < 0.1
    with pytest.raises(ValueError):
        game_user_song.generate(-1, WL, CFG)


def test_a_fixed_problem_leaves_the_seed_only_the_column_signs():
    """Every seed: the same ids and labels, every entry the same size, the
    planted coefficients mirrored with their columns, and an objective that
    reads bit for bit the same at the mirrored point."""
    wl = dict(WL, problem_seed=77)
    a = game_user_song.generate(2**31 + 5, wl, CFG)
    b = game_user_song.generate(7, wl, CFG)
    other = game_user_song.generate(7, dict(WL, problem_seed=78), CFG)
    assert np.array_equal(a["y"], b["y"])
    assert not np.array_equal(a["y"], other["y"])
    for name in ("userId", "songId"):
        assert np.array_equal(a["ids"][name], b["ids"][name])
    flipped = 0
    for k, planted in (("fixed", "fixed"), ("item", "userId")):
        xa, xb = a["shards"][k], b["shards"][k]
        assert np.array_equal(np.abs(xa), np.abs(xb))
        sign = np.sign((xa * xb).sum(axis=0))
        assert set(sign) <= {-1.0, 1.0}
        flipped += int((sign < 0).sum())
        assert np.array_equal(a["planted"][planted] * sign,
                              b["planted"][planted])
    assert 8 <= flipped <= 32  # of 40 columns, about half
    sign = np.sign((a["shards"]["item"] * b["shards"]["item"]).sum(axis=0))
    groups = reference.groups_of(a["ids"]["userId"], 200)
    w = np.random.default_rng(0).normal(size=(200, 8)) * 0.3
    offsets = jnp.zeros(3000, jnp.float32)
    fa, ga = reference.evaluate_entities(
        jnp.asarray(a["shards"]["item"]), jnp.asarray(a["y"]), offsets,
        groups, w, 1.0)
    fb, gb = reference.evaluate_entities(
        jnp.asarray(b["shards"]["item"]), jnp.asarray(b["y"]), offsets,
        groups, w * sign, 1.0)
    assert np.array_equal(fa, fb) and np.array_equal(ga, gb)


# --- the reference's pieces ------------------------------------------------
def test_groups_hold_every_row_once_in_a_lane_of_its_entity():
    ids = np.array([3, 0, 3, 3, 5, 0, 3, 3])  # entity 3: 5 rows, 0: 2, 5: 1
    groups = reference.groups_of(ids, 7)
    assert [g["index"].shape for g in groups] == [(1, 1), (1, 2), (1, 8)]
    seen = np.concatenate([g["index"][g["index"] >= 0] for g in groups])
    assert sorted(seen) == list(range(8))
    for g in groups:
        for ent, lane, n in zip(g["entities"], g["index"], g["rows"]):
            held = lane[lane >= 0]
            assert len(held) == n and np.all(ids[held] == ent)
            assert np.all(lane[n:] == -1)  # the real rows come first


def test_lane_lbfgs_is_the_single_lbfgs_lane_by_lane():
    """Uneven lanes (one converges at once, one needs the cap): every lane
    ends where ``reference/lbfgs.py`` ends on that lane alone."""
    rng = np.random.default_rng(4)
    lanes, s, d = 5, 30, 4
    x = rng.normal(size=(lanes, s, d)).astype(np.float32)
    x[1] *= np.array([1, 2, 0.5, 3], np.float32)
    x[2:] *= np.array([1, 4, 0.2, 9], np.float32)
    y = (rng.random((lanes, s)) < 0.5).astype(np.float32)
    weights = np.ones((lanes, s), np.float32)
    x[4], weights[4] = 0.0, 0.0  # a lane with no rows: gradient 0 at start
    off = np.zeros((lanes, s), np.float32)
    opts = dict(max_iterations=14, tolerance=1e-2, history=10,
                max_line_search=25)

    def fun(w):
        return reference._lanes_value_and_grad(
            x, y, off, weights, jnp.asarray(w), jnp.float32(1.0))

    batched = lbfgs_lanes(fun, np.zeros((lanes, d)), **opts)
    for e in range(lanes):
        def one(w, e=e):
            f, g = reference._lanes_value_and_grad(
                x[e:e + 1], y[e:e + 1], off[e:e + 1], weights[e:e + 1],
                jnp.asarray(w)[None], jnp.float32(1.0))
            return f[0], g[0]
        alone = lbfgs(one, np.zeros(d), **opts)
        assert batched["iterations"][e] == len(alone["values"]) - 1, e
        np.testing.assert_allclose(batched["w"][e], alone["w"], rtol=1e-5,
                                   atol=1e-7)
        assert batched["value"][e] == pytest.approx(alone["values"][-1],
                                                    rel=1e-6)
    assert batched["iterations"][4] == 0 and batched["converged"][4]
    assert len(set(batched["iterations"])) >= 3


# --- the work model and the readers ----------------------------------------
def test_work_hand_count():
    # a lane of 10 rows x 4 float32 columns read once: as work/glm.py's pass
    # (160 + 80 operations; 160 B of design, 120 B of per-row vectors), its
    # coefficients read and its gradient written (32 B)
    assert work.lanes_work(10, 1, 4) == (240.0, 160.0 + 120.0 + 32.0)
    bucket = {"rows": 30, "lanes": 3, "dim": 4, "iterations": 12,
              "evaluations": 20, "row_iterations": 100.0,
              "row_evaluations": 170.0}
    # required: every lane's rows x (its iterations + 1) = 100 + 30 row
    # passes, 12 + 3 lane passes; done: 170 row passes, 20 lane passes
    assert work.random_work([bucket], count="iterations") \
        == work.lanes_work(130, 15, 4)
    assert work.random_work([bucket], count="evaluations") \
        == work.lanes_work(170, 20, 4)
    from benchmark.work.glm import pass_work
    f, b = pass_work(1000, 32)
    assert work.fixed_work([{"rows": 1000, "dim": 32, "iterations": 9}]) \
        == (10 * f, 10 * b)


def _solve(bucket, lanes, rows, evaluations, worst, iterations, kernel,
           row_evaluations):
    return {"name": "game.re.solve", "bucket": bucket, "lanes": lanes,
            "rows": rows, "dim": 8, "kernel": kernel,
            "iterations": iterations, "evaluations": evaluations,
            "max_lane_evaluations": worst, "row_iterations": 0.0,
            "row_evaluations": row_evaluations}


SOLVES = [_solve(0, 100, 300, 900, 12, 700, "pallas", 3000.0),
          _solve(1, 10, 5000, 150, 20, 120, "pallas", 90000.0),
          _solve(2, 1, 700000, 14, 14, 12, "closed_form", 9800000.0)]


@pytest.fixture
def program(monkeypatch):
    from photon_ml_tpu.telemetry import tracing

    def hold(records):
        monkeypatch.setattr(tracing, "recorded", lambda: list(records),
                            raising=False)
    return hold


def test_reader_values_by_hand(program):
    program(SOLVES)
    run_ = {"counters": {"re_solves": 3}}
    # (900 - 100 + 150 - 10 + 14 - 1) / (700 + 120 + 12)
    assert lane_evals_per_iter.read(run_, WINDOW) == pytest.approx(953 / 832)
    # ran: 100 x 12 + 10 x 20 + 1 x 14 = 1414 lane-passes for 1064 needed
    assert lockstep_waste.read(run_, WINDOW) \
        == pytest.approx(100 * (1 - 1064 / 1414))
    share = {"value": "rows", "where": {"kernel": "pallas"}, **WINDOW}
    assert span_value_share.read(run_, share) \
        == pytest.approx(100 * 5300 / 705300)
    for reader, params in ((lane_evals_per_iter, WINDOW),
                           (lockstep_waste, WINDOW),
                           (span_value_share, share)):
        assert reader.read({"counters": {"re_solves": 4}}, params) is None
        assert reader.read({"counters": {}}, params) is None


def test_kernel_roofline_and_busy_share_by_hand():
    chip = {"busy_s": 4.0, "ops_self_s": {
        "fused_entity_value_and_grad": 2.0, "fusion": 1.0,
        "fused_value_and_grad": 0.5},
        "modules_s": {"jit__sweep_fused_impl": 3.0, "jit_train": 0.9}}
    run_ = {"trace": {"per_chip": [chip]}, "peaks": PEAKS,
            "work": {"entity_kernel_flops": 1e9, "entity_kernel_bytes": 5e11}}
    params = manifest.metric_file("re_kernel_roofline_pct")["params"]
    # 5e11 B at 1e12 B/s = 0.5 s, over the entity kernel's 2 s alone
    assert op_roofline.read(run_, params) == pytest.approx(25.0)
    run_["work"] = {"entity_kernel_flops": 0.0, "entity_kernel_bytes": 0.0}
    assert op_roofline.read(run_, params) is None
    assert op_roofline.read({**run_, "work": {}}, params) is None
    params = manifest.metric_file("re_busy_share_pct")["params"]
    assert module_busy_share.read(run_, params) == pytest.approx(75.0)
    chip["modules_s"] = {"jit_train": 0.9}
    assert module_busy_share.read(run_, params) is None


# --- whole runs ------------------------------------------------------------
def _run(capsys, name, trace=0):
    code = run.main(["--workload", name, "--seed", str(2**31 + 77),
                     "--seconds", "0.3", "--trace", str(trace)],
                    require_tpu=False)
    lines = capsys.readouterr().out.strip().splitlines()
    assert code == 0
    return json.loads(lines[-1]), lines


@pytest.mark.parametrize("cell", GAME_CELLS)
def test_a_traced_run_carries_the_cells_metrics(tiny_cells, monkeypatch,
                                                capsys, cell):
    """A whole ``--trace 1`` run here, the reduction put in by hand (no chip
    is in a CPU trace): every per-layer metric the manifest lists for the
    cell but the memory peak, each from what the program recorded in the
    window, and the warm unit's spans not among them."""
    from benchmark import trace
    from photon_ml_tpu.telemetry import tracing

    tracing.GLOBAL_TRACER._ring.clear()
    kernel_s = 1e-9
    monkeypatch.setattr(trace, "reduce", lambda path, chips: {
        "window_s": 1.0, "busy_s": 0.5, "device_ops": [], "idle_gaps": [],
        "per_chip": [{"busy_s": 0.5,
                      "modules_s": {"jit__sweep_fused_impl": 0.3,
                                    "jit_train": 0.2},
                      "ops_self_s": {"fused_entity_value_and_grad": kernel_s},
                      "collective_s": 0.0}]})
    monkeypatch.setattr(manifest, "peaks", lambda kind: PEAKS)
    # on the CPU the gate keeps the closed form: say pallas, as on the chip,
    # so that the kernel's work is not empty
    from photon_ml_tpu.ops.objective import GLMObjective
    monkeypatch.setattr(GLMObjective, "_entity_kernel_serves",
                        lambda self, design, s, d: s <= 64)
    monkeypatch.setattr(GLMObjective, "value_and_grad",
                        GLMObjective._closed_value_and_grad)
    result, lines = _run(capsys, cell, trace=1)
    got = result["metrics"]
    listed = {m["name"] for m in manifest.metrics_of(cell, "per_layer")}
    assert listed - {"hbm_peak_gib"} == set(got)
    assert got["compiles_in_window"]["value"] == 0
    assert got["re_evals_per_iter"]["value"] >= 1.0
    assert 0.0 < got["re_lockstep_waste_pct"]["value"] < 100.0
    assert 0.0 < got["re_rows_in_kernel_pct"]["value"] < 100.0
    assert got["re_busy_share_pct"]["value"] == pytest.approx(60.0)
    info = next(json.loads(l.split(": ", 1)[1]) for l in lines
                if l.startswith("info: ") and '"work"' in l)
    w = info["work"]
    assert 0 < w["entity_kernel_bytes"] < w["bytes_per_chip"] * 3
    assert w["fixed_bytes"] > 0 and w["passes"] >= 2
    for buckets in info["paths"]["buckets"].values():
        for _, _, _, kernel, rows, iterations, evaluations, worst in buckets:
            assert kernel in ("pallas", "closed_form")
            assert rows > 0 and 0 < iterations < evaluations
            assert worst >= 2


def _break(monkeypatch, fault):
    """Plant ``fault`` under ``GameEstimator.fit``, in the program."""
    import dataclasses

    from photon_ml_tpu.game import coordinate, random_effect

    if fault == "stall_after_3":
        from photon_ml_tpu.optimize import common
        whole = common.OptimizerConfig.__post_init__

        def capped(self):
            object.__setattr__(self, "max_iterations", 3)
            whole(self)
        monkeypatch.setattr(common.OptimizerConfig, "__post_init__", capped)
        return
    if fault == "stale_residual":
        # every random effect is trained against the fixed effect alone
        whole = coordinate.RandomEffectCoordinate.train
        seen = {}

        def stale(self, offsets, warm_start=None, sweep=0):
            first = seen.setdefault("offsets", offsets)
            return whole(self, first, warm_start, sweep)
        monkeypatch.setattr(coordinate.RandomEffectCoordinate, "train", stale)
        return
    whole = random_effect.RandomEffectSolver.train

    def broken(self, dataset, offsets, lam, warm_start=None, dim=None):
        model, scores = whole(self, dataset, offsets, lam, warm_start, dim)
        coeffs = np.array(model.coeffs)
        ents = np.asarray(model.keys) // model.dim
        if fault == "half_entities":
            coeffs[ents % 2 == 1] = 0.0
            return dataclasses.replace(model, coeffs=coeffs), scores
        keep = ents % 7 != 0  # "entities_dropped": no coefficients at all
        return dataclasses.replace(
            model, keys=np.asarray(model.keys)[keep],
            coeffs=coeffs[keep]), scores
    monkeypatch.setattr(random_effect.RandomEffectSolver, "train", broken)


@pytest.mark.parametrize("fault", ["stall_after_3", "stale_residual",
                                   "half_entities", "entities_dropped"])
@pytest.mark.parametrize("cell", GAME_CELLS)
def test_broken_game_path_is_not_correct(tiny_cells, monkeypatch, capsys,
                                         cell, fault):
    _break(monkeypatch, fault)
    result, _ = _run(capsys, cell)
    assert result["correct"] is False
    over = {n for n, c in result["compared"].items()
            if c["value"] > c["limit"]}
    assert len(over) >= 2, over
    if fault == "entities_dropped":
        assert {"user_missing_share", "song_missing_share"} <= over
    if fault == "stale_residual":
        # the first random effect's residual IS the fixed effect's scores
        assert not any(n.startswith(("fixed_", "user_")) for n in over), over
