"""On-chip dense vs chunked-sparse fixed-effect layout crossover probe.

Measures one jitted ``value_and_grad`` iteration of the logistic GLM
objective for the SAME synthetic problem in both layouts across a
(dim, nnz-per-row) grid, prints the table, and reports the measured
crossover: the largest dense dim (per nnz/row) at which the dense-padded
design still beats :class:`~photon_ml_tpu.ops.design.ChunkedSparseDesign`.

The result feeds ``photon_ml_tpu/game/data.py::choose_dense_design``
(the automatic layout pick — SURVEY.md §7 hard-part #2);
the measured table lives in that function's docstring (taken 2026-07-31,
before PR 1; not measured on the present chip). Re-run this script on the
chip after any toolchain bump:

    PYTHONPATH=. python tools/layout_crossover.py

Expected model: the dense iteration streams ``n*d*4`` bytes at the HBM rate,
the sparse one pays XLA's random-gather cost per element on ``n*k`` entries
plus chunk overhead, so dense wins roughly while
``d <= (gather_ns * HBM_GBps / 4) * k``. With the 2026-07-31 figures
(~340 GB/s, ~7 ns/element) that is ``600 * k``; the probe measures the
constant.
"""

import time

import numpy as np


def bench_layouts(n, d, k, reps=8, seed=0):
    import jax
    import jax.numpy as jnp

    from photon_ml_tpu.ops.design import ChunkedSparseDesign, DenseDesign
    from photon_ml_tpu.ops.losses import LogisticLoss
    from photon_ml_tpu.ops.objective import GLMData, GLMObjective

    rng = np.random.default_rng(seed)
    rows = np.repeat(np.arange(n, dtype=np.int64), k)
    cols = rng.integers(0, d, size=n * k).astype(np.int32)
    vals = (rng.normal(size=n * k) / np.sqrt(k)).astype(np.float32)
    labels = (rng.uniform(size=n) < 0.5).astype(np.float32)
    w = jnp.asarray(rng.normal(size=d).astype(np.float32))
    obj = GLMObjective(LogisticLoss)

    def problem(design):
        return GLMData(design=design, labels=jnp.asarray(labels),
                       offsets=jnp.zeros(n, jnp.float32),
                       weights=jnp.ones(n, jnp.float32))

    step = jax.jit(lambda w, data: obj.value_and_grad(w, data, 1e-3))

    def run(design):
        # Iterations are CHAINED (w updated from the grad) so each rep is a
        # genuine data-dependent execution — like real solver iterations —
        # and the final float() (a device→host pull) waits for the whole
        # chain.
        data = problem(design)
        wi = w
        v, g = step(wi, data)
        _ = float(v)  # compile + warm barrier
        t0 = time.perf_counter()
        for _ in range(reps):
            v, g = step(wi, data)
            wi = wi - 1e-4 * g
        _ = float(v)
        return (time.perf_counter() - t0) / reps

    # min of two independent passes per layout: the first timed pass after
    # a fresh compile can carry one-time costs; the repeat is the steady
    # state
    dense_bytes = n * d * 4
    t_dense = None
    if dense_bytes <= 4 << 30:  # don't OOM the probe itself
        x = np.zeros((n, d), np.float32)
        x[rows, cols.astype(np.int64)] = vals
        design = DenseDesign(x=jnp.asarray(x))
        t_dense = min(run(design), run(design))
        del x, design
    sp = ChunkedSparseDesign.from_coo(
        rows.astype(np.int32), cols, vals, n_rows=n, n_cols=d)
    t_sparse = min(run(sp), run(sp))
    return t_dense, t_sparse


def main():
    import jax

    from photon_ml_tpu import compile_cache

    # 18 (d, k) points x 2 layouts in this grid: keep the compiles
    compile_cache.configure()

    print(f"devices: {jax.devices()}")
    print(f"{'d':>7} {'k':>4} {'n':>8} {'dense_ms':>9} {'sparse_ms':>10} "
          f"{'winner':>7} {'ratio':>6}")
    results = []
    for d in (512, 2048, 4096, 8192, 16384, 65536):
        for k in (8, 32, 128):
            if k >= d:
                continue
            n = int(max(20_000, min(400_000, 1_000_000_000 // (4 * d))))
            t_dense, t_sparse = bench_layouts(n, d, k)
            if t_dense is None:
                print(f"{d:>7} {k:>4} {n:>8} {'skip':>9} "
                      f"{t_sparse*1e3:>10.2f} {'sparse':>7} {'':>6}")
                continue
            win = "dense" if t_dense <= t_sparse else "sparse"
            ratio = t_sparse / t_dense
            results.append((d, k, win))
            print(f"{d:>7} {k:>4} {n:>8} {t_dense*1e3:>9.2f} "
                  f"{t_sparse*1e3:>10.2f} {win:>7} {ratio:>6.2f}")
    # report measured crossover constant: max d/k where dense still wins
    cs = [d / k for d, k, win in results if win == "dense"]
    if cs:
        print(f"\nmax d/k with dense winning: {max(cs):.0f}")


if __name__ == "__main__":
    main()
