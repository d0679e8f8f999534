"""What a CPU can check of the chip bring-up (PR 21).

The chip itself is proved by ``chip_smoke.py`` through the chip tool; these
lock the behaviours around it that need no device: nothing falls back
quietly, one process owns the chip, the compile cache is placed from
outside, and the entity kernel's VMEM plan counts what the kernel allocates.
"""

import logging
import os
import subprocess
import sys
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_chip_smoke_refuses_a_cpu_backend():
    """Non-zero exit in seconds, before any data is generated, and no
    result line."""
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "chip_smoke.py")],
        env={**os.environ, "JAX_PLATFORMS": "cpu"}, cwd=REPO,
        capture_output=True, text=True, timeout=120)
    assert proc.returncode != 0
    assert "backend cpu" in proc.stdout
    assert '"ok"' not in proc.stdout and "leg " not in proc.stdout
    assert "not 'tpu'; nothing was run" in proc.stderr


def test_chip_smoke_result_line_is_the_contract_and_nothing_more():
    """The run's verdict is one JSON object with exactly ``ok`` and
    ``device`` {platform, kind, count}; the per-leg report is another line."""
    import json

    sys.path.insert(0, REPO)
    try:
        import chip_smoke
    finally:
        sys.path.remove(REPO)
    line = chip_smoke.result_line(
        {"platform": "tpu", "kind": "TPU v5 lite", "count": 1})
    assert "\n" not in line
    assert json.loads(line) == {"ok": True, "device": {
        "platform": "tpu", "kind": "TPU v5 lite", "count": 1}}


class TestCompileCache:
    @pytest.fixture(autouse=True)
    def _restore(self):
        before = jax.config.jax_compilation_cache_dir
        yield
        jax.config.update("jax_compilation_cache_dir", before)

    def test_environment_placement_is_left_alone(self, monkeypatch):
        from photon_ml_tpu import compile_cache

        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/some/dir")
        before = jax.config.jax_compilation_cache_dir
        assert compile_cache.configure() is None
        assert jax.config.jax_compilation_cache_dir == before

    def test_default_is_the_checkout(self, monkeypatch):
        from photon_ml_tpu import compile_cache

        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
        path = compile_cache.configure()
        assert path == os.path.join(REPO, ".jax_cache")
        assert jax.config.jax_compilation_cache_dir == path


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("s,d", [(8, 4), (16, 8), (95, 7), (174, 8),
                                 (600, 8), (33, 200)])
def test_entity_plan_counts_what_the_kernel_allocates(s, d, dtype):
    """The plan's bytes per entity are the kernel's double-buffered operand
    blocks with the entities in the lane dimension, by Mosaic's tile rules
    (re-derived here for a block of 128 lanes: every tile is full), and the
    planned block, with what the body keeps live, stays inside the 16 MiB
    scoped limit."""
    from photon_ml_tpu.ops import pallas_re

    def tiles(rows, itemsize):  # a (rows, 128 lanes) array's bytes
        sublane = 8 * 4 // itemsize
        return (-(-rows // sublane) * sublane) * 128 * itemsize

    itemsize = jnp.dtype(dtype).itemsize
    # the layout pads a lane's rows to the stored design's row tile
    rows = -(-s // (32 // itemsize)) * (32 // itemsize)
    # per 128 entities: x (D, S, 128) stored; labels/offsets/weights
    # (S, 128) f32; w and grad (D, 128) f32; the value (1, 128) f32
    operands = (d * tiles(rows, itemsize) + 3 * tiles(rows, 4)
                + 2 * tiles(d, 4) + tiles(1, 4))
    per_entity = pallas_re._entity_bytes(s, d, dtype)
    assert per_entity * 128 == 2 * operands
    block, _ = pallas_re.entity_plan(10**6, s, d, dtype)
    assert block % 128 == 0
    # the body's tiles: D upcast columns, D coefficients, D + 1 sums at the
    # least, each a row tile by 128 lanes of float32
    body = pallas_re._body_bytes(d, dtype)
    assert body >= (3 * d + 1) * tiles(32 // itemsize, 4)
    assert block * per_entity + body \
        <= pallas_re.VMEM_BUDGET_BYTES < 16 << 20


def test_a_declining_gate_says_which_predicate(caplog):
    from photon_ml_tpu.ops import objective as obj
    from photon_ml_tpu.ops.design import DenseDesign
    from photon_ml_tpu.ops.losses import LogisticLoss

    obj._log_declined.cache_clear()
    data = obj.GLMData(DenseDesign(jnp.zeros((16, 3))), jnp.zeros(16),
                       jnp.zeros(16), jnp.ones(16))
    with caplog.at_level(logging.INFO, logger=obj.__name__):
        fused = obj.GLMObjective(LogisticLoss, fused=True)
        assert not fused._fused_eligible(data)
        assert not fused._fused_eligible(data)  # said once, not per call
        entity = obj.GLMObjective(LogisticLoss, fused_entity=True,
                                  fused_interpret=True)
        assert entity.entity_pad(
            jnp.zeros((4, 4096, 256), jnp.float32)) == 0
        assert not obj.GLMObjective(LogisticLoss)._fused_eligible(data)
    said = [r.getMessage() for r in caplog.records]
    assert said == [
        "pallas_glm declined: backend is 'cpu', not 'tpu' — XLA closed form",
        "pallas_re declined: a 128-entity block of float32 (4096, 256) lanes "
        "exceeds the kernel's VMEM budget — XLA closed form"]


class TestDivergenceGuardScope:
    """Only a non-finite result is divergence: under a guard any other
    exception from a coordinate's train propagates."""

    def _run(self, error):
        from photon_ml_tpu.game.coordinate_descent import CoordinateDescent
        from photon_ml_tpu.resilience import (
            DivergenceGuard,
            DivergencePolicy,
        )
        from photon_ml_tpu.types import TaskType

        class Refuses:
            lam = 1.0

            def train(self, residual, warm_start, sweep=0):
                raise error

        good = types.SimpleNamespace(score=lambda data: np.zeros(4))
        data = types.SimpleNamespace(n_samples=4,
                                     offsets=np.zeros(4, np.float32))
        return CoordinateDescent(["c"]).run(
            {"c": Refuses()}, data, TaskType.LOGISTIC_REGRESSION,
            initial_models={"c": good},
            guard=DivergenceGuard(DivergencePolicy(mode="freeze")))

    def test_compiler_refusal_propagates_under_freeze(self):
        with pytest.raises(RuntimeError, match="Mosaic refused"):
            self._run(RuntimeError("Mosaic refused the kernel"))

    def test_non_finite_report_still_freezes(self):
        result = self._run(FloatingPointError("invalid value (nan)"))
        assert set(result.model.coordinates) == {"c"}  # the last good model


class TestOneProcessOwnsTheChip:
    def _supervised(self, monkeypatch, tmp_path, n):
        from photon_ml_tpu.cli import train_game
        from photon_ml_tpu.resilience import supervisor

        monkeypatch.setattr(
            supervisor.FleetSupervisor, "run",
            lambda self: supervisor.FleetResult(0, 1, {"ran": True}))
        return train_game.run([
            "--training-data", "unused.avro", "--output-dir", str(tmp_path),
            "--feature-shards", "g=g", "--coordinates",
            "global=fixed,shard=g", "--update-sequence", "global",
            "--supervise", str(n), "--telemetry-dir", str(tmp_path / "t"),
            "--telemetry-poll-s", "0.01"])

    def test_supervisor_parent_stays_off_jax(self, monkeypatch, tmp_path):
        """--telemetry-poll-s starts a device sampler in a worker; in the
        supervising parent it would initialize the backend and take the
        chip from every worker."""
        touched = []
        for name in ("devices", "local_devices", "default_backend",
                     "device_count"):
            monkeypatch.setattr(jax, name,
                                lambda *a, _n=name, **k: touched.append(_n))
        out = self._supervised(monkeypatch, tmp_path, 1)
        assert out["ran"] and out["restarts"] == 0
        assert touched == []

    def test_local_fleet_refused_where_workers_would_open_a_tpu(
            self, monkeypatch, tmp_path):
        monkeypatch.delenv("JAX_PLATFORMS", raising=False)  # libtpu is here
        with pytest.raises(SystemExit, match="claim every chip"):
            self._supervised(monkeypatch, tmp_path, 2)
        monkeypatch.setenv("JAX_PLATFORMS", "cpu")
        assert self._supervised(monkeypatch, tmp_path, 2)["ran"]


def test_score_memory_guard_does_not_guess_on_a_tpu(monkeypatch):
    from photon_ml_tpu.game import coordinate_descent as cd

    def device(platform, stats):
        return types.SimpleNamespace(platform=platform,
                                     memory_stats=lambda: stats)

    monkeypatch.setattr(jax, "local_devices", lambda: [
        device("tpu", {"bytes_limit": 16 << 30}),
        device("tpu", {"bytes_limit": 8 << 30})])
    assert cd._device_memory_bytes() == 8 << 30  # must fit on EVERY device
    monkeypatch.setattr(jax, "local_devices",
                        lambda: [device("tpu", {"bytes_in_use": 0})])
    with pytest.raises(RuntimeError, match="bytes_limit"):
        cd._device_memory_bytes()
    monkeypatch.setattr(jax, "local_devices", lambda: [device("cpu", None)])
    assert cd._device_memory_bytes() == cd._UNACCOUNTED_DEVICE_BYTES


def test_native_artifact_is_keyed_on_sources_flags_and_host(monkeypatch):
    """A library built for another CPU, other flags or other sources is a
    different file: a copied checkout rebuilds instead of loading it."""
    from photon_ml_tpu import native

    here = native._artifact_path()
    assert here.startswith(native._BUILD_DIR) and native.available()
    monkeypatch.setattr(native, "_host_cpu", lambda: "another cpu")
    elsewhere = native._artifact_path()
    monkeypatch.setattr(native, "_FLAGS", native._FLAGS + ("-g",))
    assert len({here, elsewhere, native._artifact_path()}) == 3
