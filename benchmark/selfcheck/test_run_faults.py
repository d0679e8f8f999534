"""A whole run (``benchmark.run.main`` without its look for a chip) with the
timed path broken underneath: ``correct`` has to come out false, once for
each fault a training cell can have. And true when nothing is broken."""

import json

import jax
import pytest

from benchmark import manifest, run

CELLS = {w["name"]: w for w in manifest.benchmark()["workloads"]}
#: the GLM family's cells, by their configuration's ``family``, each with the
#: faults it can have: the exchange can be left out only where chips exchange
GLM_FAULTS = [
    (name, fault) for name in sorted(CELLS)
    if manifest.cell(name)[2]["family"] == "glm"
    for fault in ["state_unchanged", "stall_after_3", "warm_start_returned",
                  "half_batch"] + ["no_exchange"] * (CELLS[name]["chips"] > 1)]


def _run(capsys, name):
    if len(jax.devices()) < CELLS[name]["chips"]:
        pytest.skip(f"needs {CELLS[name]['chips']} (virtual) devices")
    code = run.main(["--workload", name, "--seed", str(2**31 + 77),
                     "--seconds", "0.5", "--trace", "0"], require_tpu=False)
    out = capsys.readouterr()
    result = json.loads(out.out.strip().splitlines()[-1])
    assert code == 0
    assert list(result)[-1] == "compared"
    for name_, c in result["compared"].items():
        assert f"compared {name_} " in out.err
    return result


@pytest.mark.parametrize("name", sorted(CELLS))
def test_sound_run_is_correct(tiny_cells, capsys, name):
    result = _run(capsys, name)
    assert result["correct"] is True, result["compared"]
    assert result["attempted"] >= 1 and result["failed"] == 0
    assert set(result["metrics"]) == {
        m["name"] for m in manifest.metrics_of(name, "end_to_end")}
    assert all(v["value"] > 0 for v in result["metrics"].values())


def _break_glm(monkeypatch, fault):
    """Plant ``fault`` in ``train_glm_sweep`` as the family calls it."""
    import dataclasses

    import jax.numpy as jnp
    from photon_ml_tpu.glm import training

    whole = training.train_glm_sweep

    def with_optimizer(config, **changed):
        return dataclasses.replace(config, optimizer_config=dataclasses.replace(
            config.optimizer_config, **changed))

    def broken(task, data, weights, config, **kw):
        if fault == "state_unchanged":
            # the coefficients as they were before training, the reports kept
            trained = whole(task, data, weights, config, **kw)
            zero = jnp.zeros_like(trained[0].result.w)
            return [dataclasses.replace(t, result=dataclasses.replace(
                t.result, w=zero)) for t in trained]
        if fault == "stall_after_3":
            # every solve's state unchanged after its third iteration, its
            # reports consistent with where it stands
            return whole(task, data, weights,
                         with_optimizer(config, max_iterations=3), **kw)
        if fault == "warm_start_returned":
            # the first weight solved soundly, the later solves hand their
            # warm start back with a consistent value and gradient norm (a
            # tolerance that the start already meets)
            first, *later = sorted(weights, reverse=True)
            head = whole(task, data, [first], config, **kw)
            return head + whole(
                task, data, later, with_optimizer(config, tolerance=1e30),
                **{**kw, "initial": head[0].result.w})
        if fault == "half_batch":
            half = data.labels.shape[-1] // 2
            cut = lambda a: a[..., :half]
            data = dataclasses.replace(
                data, design=dataclasses.replace(
                    data.design, x=data.design.x[..., :half, :]),
                labels=cut(data.labels), offsets=cut(data.offsets),
                weights=cut(data.weights))
            return whole(task, data, weights, config, **kw)
        if fault == "no_exchange":
            # the chips' losses and gradients left un-summed: the first
            # chip's answer on its own rows, reported as the whole
            import jax

            kw = {k: v for k, v in kw.items() if k not in ("mesh", "dim")}
            return whole(task, jax.tree.map(lambda a: a[0], data), weights,
                         config, **kw)
        raise ValueError(fault)

    monkeypatch.setattr(training, "train_glm_sweep", broken)


@pytest.mark.parametrize("name, fault", GLM_FAULTS)
def test_broken_glm_path_is_not_correct(tiny_cells, monkeypatch, capsys,
                                        name, fault):
    _break_glm(monkeypatch, fault)
    result = _run(capsys, name)
    assert result["correct"] is False
    if fault in ("stall_after_3", "warm_start_returned"):
        # consistent reports: only the path's own numbers can see these
        over = {n for n, c in result["compared"].items()
                if c["value"] > c["limit"]}
        assert over and all(n.endswith(("_loss_gap", "_move_gap"))
                            and n.startswith(("solve1_", "later_"))
                            for n in over), over
