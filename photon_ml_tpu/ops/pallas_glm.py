"""Pallas TPU kernel: fused GLM objective value + gradient in ONE pass over X.

Why: XLA computes ``value_and_grad`` of the GLM objective as two passes over
the design matrix — forward margins (``X @ w``) and transposed gradient
(``X^T @ dl``) — so the HBM-bound solve reads X twice per L-BFGS iteration.
This kernel streams each row-block of X through VMEM once and computes BOTH
contractions while the block is resident (the counterpart of the
reference's single-pass per-partition ``ValueAndGradientAggregator.scala``,
which also fuses margin/loss/gradient in one sweep per sample):

    per block i:   m   = w·X_iᵀ + offsets_i           (MXU, 1-row matmul)
                   l  += Σ weights_i * loss(m, y_i)   (VPU)
                   g  += (weights_i * dl(m)) · X_i    (MXU, 1-row matmul)

Layout is the whole game (this is round 2 of this kernel; round 1 lost to
XLA): every vector lives LANE-MAJOR — labels/offsets/weights/margins as
``(1, B)`` rows, the gradient accumulator as ``(1, D)`` — so there are no
``(N, 1)`` layouts (which waste 127/128 lanes per VMEM tile) and no
``(B, 1) → (1, B)`` relayouts inside the loop. Both contractions are 1-row
matmuls against the SAME resident x block:

    margins  (1,B) = dot_general(w (1,D), x (B,D), contract D with D)
    grad    +(1,D) = dot_general(dvec (1,B), x (B,D), contract B with rows)

How fast it is on the present chip is the benchmark's to say (PERF.md §5:
on a TPU v5e the f32 kernel moves 1.5M x 1024 at about 71% of the chip's
819 GB/s; ``glm_kernel_roofline_pct`` is its metric). Every figure below was
measured on a TPU v5e between 2026-07-30 and 2026-08-01, before PR 1, through
a device runtime that no longer exists, and none has been measured on the
present chip: they record why the kernel has the shape it has, relative to
one another, not how fast it is. At (200k, 1024), 50-iteration compiled loop
(objective evaluation only):

    XLA two-pass closed form       3.61 ms/iter   (453 GB/s effective)
    this kernel, f32 (HIGHEST)     2.65 ms/iter   (1.36x)
    this kernel, f32, fast-matmul  2.44 ms/iter   (but ~1e-3 gradients — see
                                                   precision note in _kernel)
    this kernel, bf16, B=1024      1.85 ms/iter   (1.95x; design stored bf16)

Round-2 block-size sweep (same shape, 50-iter fori_loop, best of 3):

    f32  B=400 (auto)   2.658 ms/iter   308 GB/s effective
    f32  B=800          VMEM OOM (19.7 MB scoped > 16 MB limit)
    bf16 B=800 (auto)   1.947 ms/iter   210 GB/s eff
    bf16 B=1000         3.890 ms/iter   (sublane-hostile: 1000 % 16 != 0
                        after rounding → padding path)
    bf16 B=1600         2.630 ms/iter
    bf16 B=2000         1.908 ms/iter   215 GB/s eff

bf16 is NOT bandwidth-bound: halving the bytes recovered only 1.37x over
fused f32, flat across block sizes — the M=1 matvec shape leaves 127/128
MXU rows idle, so at bf16's byte rate the kernel hits the issue/compute
wall (~210 GB/s effective) before the HBM wall. End-to-end the
bf16-design solve still measures ~1.4–1.5x over the f32 fused solve
(101 ms vs 150 ms, 50 iterations) because line-search evaluations share
the same kernel. Auto block sizes (f32 400, bf16 800) are within 2% of
the best measured; no retune needed.

Round-4 multi-row-margin variant (``fused_value_and_grad_multi`` + the
``vmappable_value_and_grad`` custom-vmap wrapper — the batched
lambda-sweep consumer): M coefficient rows share one pass over X; margins
are M rows of one MXU matmul. Dense 200k x 1024, 5 lambdas, 50-iteration
solves, D2H-sync, min of 3:

    batched sweep, unfused under vmap (round 3)   1.27 s
    batched sweep + multi-row kernel              0.95 s   (1.33x better)
    sequential sweep (M=1 kernel + warm starts)   0.74 s   (still the
                                                  dense winner)

Verdict: the idle MXU rows are real and the multi-row kernel recovers a
1.33x on the batched path, but warm starts (late lanes converge in a few
iterations) still beat lockstep lanes on dense problems — the sweep
default (sequential for dense, batched for chunked-sparse at its 1.74x)
stands. The kernel pays off when lanes genuinely must run without warm
starts (the vmapped batched mode users opt into). Only chained/in-solve
measurements were taken: a standalone call's wall is mostly its D2H round
trip.

In auto mode the block size prefers the largest ≤-cap divisor of n (see
``_dividing_block_rows``; at n=200k f32 that's B=400) so X streams in
place — padding the row dim means `jnp.pad` copying the FULL design inside
the traced objective on every evaluation, which more than erased the
kernel's win inside the L-BFGS loop when first measured. End to end: the
bench solve (50 iterations) runs 0.145 s fused vs 0.196 s closed-form
(1.35x), converging to the same objective value.

Alternatives measured and rejected: the round-1 sublane-major formulation
(2.6–6.9 ms); per-block output slots with a ``parallel`` grid + outside
reduction (2.68 ms f32 — the revisited accumulator is NOT the bottleneck);
larger f32 blocks (B=2048 exceeds the 16 MB VMEM scoped limit).

Enabled via ``GLMObjective(fused=True)`` for dense designs with identity
normalization; other cases fall back to autodiff transparently. L2 stays
outside (coefficient-space term). The bf16 path is opt-in by storing the
design bf16 — margins/loss/gradient still accumulate f32 on the MXU, but
the design itself is rounded (~3 decimal digits), which perturbs the
optimum; keep f32 where reference-parity matters.

Grid iteration on TPU is sequential, so accumulating into the outputs across
grid steps (init at block 0) is the standard reduction pattern.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from photon_ml_tpu.ops.losses import PointwiseLoss

#: rows streamed per grid step, by design dtype: the f32 sweet spot is the
#: largest block whose double-buffered DMA fits scoped VMEM; bf16 blocks are
#: half the bytes so twice the rows.
DEFAULT_BLOCK_ROWS_F32 = 512
DEFAULT_BLOCK_ROWS_BF16 = 1024


def _kernel(loss: PointwiseLoss, x_ref, y_ref, off_ref, wt_ref, w_ref,
            loss_ref, grad_ref):
    i = pl.program_id(0)

    @pl.when(i == 0)
    def _init():
        loss_ref[:] = jnp.zeros_like(loss_ref)
        grad_ref[:] = jnp.zeros_like(grad_ref)

    x = x_ref[:]  # (B, D) — read once, used by both contractions
    w = w_ref[:]  # (1, D) f32
    y = y_ref[0]  # (1, B) — block i of the (n_blocks, 1, B) reshaped vector
    off = off_ref[0]
    wt = wt_ref[0]

    # precision=HIGHEST for f32 designs: the MXU's default f32 handling is
    # a single bf16 pass (~1e-3 relative — measured 40x worse gradients
    # than the XLA closed form, enough to disturb L-BFGS paths); HIGHEST
    # selects the multi-pass f32 emulation at no wall-clock cost (the
    # kernel is HBM-bound). bf16 designs keep DEFAULT — requesting an
    # fp32-contract on bf16 operands is rejected by Mosaic ("Bad lhs
    # type"), and bf16 storage has already rounded the data anyway.
    precision = (jax.lax.Precision.HIGHEST if x.dtype == jnp.float32
                 else jax.lax.Precision.DEFAULT)
    m = jax.lax.dot_general(
        w.astype(x.dtype), x,
        dimension_numbers=(((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32,
        precision=precision) + off  # (1, B)
    # padded rows carry weight 0: evaluate them at margin 0 (finite) AND
    # zero-weight the output — the double-where guard of GLMObjective.value
    live = wt > 0
    m_safe = jnp.where(live, m, 0.0)
    lvec = loss.loss(m_safe, y)
    dvec = jnp.where(live, loss.d1(m_safe, y) * wt, 0.0)
    loss_ref[:] += jnp.sum(jnp.where(live, wt * lvec, 0.0)).reshape(1, 1)
    grad_ref[:] += jax.lax.dot_general(
        dvec.astype(x.dtype), x,
        dimension_numbers=(((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
        precision=precision)  # (1, D)


def _out_struct(x, shape, dtype):
    """ShapeDtypeStruct for a kernel output, carrying the input's varying
    manual axes: under shard_map, outputs vary over the same mesh axes as
    the design block — without the vma the checker rejects the
    pallas_call. One home for both kernels so the plumbing cannot drift."""
    vma = jax.typeof(x).vma or None
    return (jax.ShapeDtypeStruct(shape, dtype) if vma is None
            else jax.ShapeDtypeStruct(shape, dtype, vma=vma))


def _default_block_rows(dtype) -> int:
    if dtype == jnp.bfloat16:
        return DEFAULT_BLOCK_ROWS_BF16
    return DEFAULT_BLOCK_ROWS_F32


def _sublane_tile(dtype) -> int:
    """Minimum second-to-last block dim for this dtype (Mosaic tiling)."""
    return 16 if dtype == jnp.bfloat16 else 8


def _dividing_block_rows(n: int, cap: int, tile: int) -> int | None:
    """Largest tile-aligned divisor of ``n`` that is ≤ cap and ≥ 128.

    A block size that divides ``n`` lets the kernel stream X in place. The
    alternative — padding the row dim — is `jnp.pad` of the FULL design
    inside the traced objective, a copy of the dominant payload on every
    evaluation (measured: it more than erased the kernel's win inside the
    L-BFGS loop). Below 128 rows the grid gets long and per-block overhead
    wins; fall back to the padding path instead. ``tile`` is the dtype's
    sublane tile (8 for f32, 16 for bf16) — a block that is a multiple of 8
    but not 16 fails Mosaic lowering for a bf16 design.
    """
    for b in range(min(cap, n) // tile * tile, 127, -tile):
        if n % b == 0:
            return b
    return None


def _rounded_block(n: int, cap: int, tile: int) -> int:
    """Tile-align ``cap`` against ``n`` rows — a block covering the whole
    (unpadded) array is accepted as-is by Mosaic, anything smaller must be a
    multiple of the dtype's sublane tile."""
    b = min(cap, max(n, tile))
    if b < n:
        b = max(tile, b // tile * tile)
    return b


def auto_block_rows(n: int, dtype) -> int | None:
    """The block size auto mode will stream with NO per-call copy, or None.

    ``None`` means :func:`fused_value_and_grad` in auto mode would have to
    ``jnp.pad`` the full design inside the traced objective on every
    evaluation — the regression documented in :func:`_dividing_block_rows`.
    Callers (``GLMObjective.value_and_grad``) use this to fall back to the
    XLA closed form for such shapes instead of paying the copy. This IS the
    kernel's auto-mode selection (``fused_value_and_grad`` calls it), so the
    predicate cannot drift from the executor.
    """
    tile = _sublane_tile(dtype)
    b = _rounded_block(n, _default_block_rows(dtype), tile)
    if n % b == 0:
        return b
    return _dividing_block_rows(n, _default_block_rows(dtype), tile)


@functools.partial(jax.jit, static_argnames=("loss", "block_rows", "interpret"))
def fused_value_and_grad(loss: PointwiseLoss, x, w, labels, offsets, weights,
                         *, block_rows: int | None = None,
                         interpret: bool = False):
    """(value, grad) of ``Σ_i weights_i * loss(x_i·w + offsets_i, y_i)``.

    ``x`` is ``(n, d)`` (f32, or bf16 for the half-bandwidth path), ``w``
    ``(d,)`` f32. Rows are processed in ``block_rows`` chunks; the tail
    block is padded with weight-0 rows, which contribute exactly nothing.
    """
    n, d = x.shape
    tile = _sublane_tile(x.dtype)
    if block_rows is None:
        # auto mode prefers a dividing block (no-copy); one shared selector
        # (auto_block_rows) so the objective's skip-predicate cannot drift
        b = auto_block_rows(n, x.dtype)
        if b is None:  # no dividing block: padding path
            b = _rounded_block(n, _default_block_rows(x.dtype), tile)
    else:
        # an explicit block_rows is honored (tile-rounded), padding if needed
        b = _rounded_block(n, block_rows, tile)
    n_blocks = pl.cdiv(n, b)
    n_pad = n_blocks * b
    if n_pad != n:
        pad = n_pad - n
        x = jnp.pad(x, ((0, pad), (0, 0)))
        labels = jnp.pad(labels, (0, pad))
        offsets = jnp.pad(offsets, (0, pad))
        weights = jnp.pad(weights, (0, pad))

    f32 = jnp.float32
    itemsize = jnp.dtype(x.dtype).itemsize
    # vectors ride as (n_blocks, 1, b) — a free reshape — so the per-step
    # block (1, 1, b) has its last two dims equal to the array's own; Mosaic
    # otherwise requires (8k, 128k) block dims, which would force b to be a
    # multiple of 128 and usually rule out the no-copy dividing block size
    out = pl.pallas_call(
        functools.partial(_kernel, loss),
        # the operation's name in a profiler trace, held here so that a
        # refactoring cannot rename what the benchmark's readers match
        # (benchmark/metrics/glm_kernel_roofline_pct.json)
        name="fused_value_and_grad",
        grid=(n_blocks,),
        in_specs=[
            pl.BlockSpec((b, d), lambda i: (i, 0), memory_space=pltpu.VMEM),
            pl.BlockSpec((1, 1, b), lambda i: (i, 0, 0), memory_space=pltpu.VMEM),
            pl.BlockSpec((1, 1, b), lambda i: (i, 0, 0), memory_space=pltpu.VMEM),
            pl.BlockSpec((1, 1, b), lambda i: (i, 0, 0), memory_space=pltpu.VMEM),
            pl.BlockSpec((1, d), lambda i: (0, 0), memory_space=pltpu.VMEM),
        ],
        out_specs=[
            pl.BlockSpec((1, 1), lambda i: (0, 0), memory_space=pltpu.VMEM),
            pl.BlockSpec((1, d), lambda i: (0, 0), memory_space=pltpu.VMEM),
        ],
        out_shape=[
            _out_struct(x, (1, 1), f32),
            _out_struct(x, (1, d), f32),
        ],
        cost_estimate=pl.CostEstimate(
            flops=4 * n_pad * d,
            transcendentals=2 * n_pad,
            bytes_accessed=n_pad * d * itemsize,
        ),
        interpret=interpret,
    )(
        x,
        labels.astype(f32).reshape(n_blocks, 1, b),
        offsets.astype(f32).reshape(n_blocks, 1, b),
        weights.astype(f32).reshape(n_blocks, 1, b),
        w.astype(f32).reshape(1, -1),
    )
    value, grad = out
    return value[0, 0], grad[0, :]


def _kernel_multi(loss: PointwiseLoss, x_ref, y_ref, off_ref, wt_ref, w_ref,
                  loss_ref, grad_ref):
    """Multi-row-margin variant: M coefficient rows share ONE pass over the
    design block. The M=1 kernel leaves 127/128 MXU rows idle (the issue
    wall the measurement table documents); here margins are the (M, B) rows
    of a single matmul and the gradient a real (M, B)x(B, D) matmul — the
    batched lambda-sweep's lanes ride the idle rows for free."""
    i = pl.program_id(0)

    @pl.when(i == 0)
    def _init():
        loss_ref[:] = jnp.zeros_like(loss_ref)
        grad_ref[:] = jnp.zeros_like(grad_ref)

    x = x_ref[:]  # (B, D) — read once, shared by every lane
    w = w_ref[:]  # (M, D) f32
    y = y_ref[0]  # (1, B)
    off = off_ref[0]
    wt = wt_ref[0]
    precision = (jax.lax.Precision.HIGHEST if x.dtype == jnp.float32
                 else jax.lax.Precision.DEFAULT)
    m = jax.lax.dot_general(
        w.astype(x.dtype), x,
        dimension_numbers=(((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32,
        precision=precision) + off  # (M, B); off broadcasts over lanes
    live = wt > 0  # (1, B) — broadcasts
    m_safe = jnp.where(live, m, 0.0)
    lvec = loss.loss(m_safe, y)
    dvec = jnp.where(live, loss.d1(m_safe, y) * wt, 0.0)
    loss_ref[:] += jnp.sum(jnp.where(live, wt * lvec, 0.0),
                           axis=1).reshape(1, -1)  # (1, M)
    grad_ref[:] += jax.lax.dot_general(
        dvec.astype(x.dtype), x,
        dimension_numbers=(((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
        precision=precision)  # (M, D)


@functools.partial(jax.jit, static_argnames=("loss", "block_rows", "interpret"))
def fused_value_and_grad_multi(loss: PointwiseLoss, x, ws, labels, offsets,
                               weights, *, block_rows: int | None = None,
                               interpret: bool = False):
    """(values (M,), grads (M, D)) for M coefficient vectors over ONE pass
    of the design — the batched lambda-sweep consumer (every lane shares
    the same data; only w differs per lane). Block selection and padding
    semantics are identical to :func:`fused_value_and_grad`.

    KEPT SEPARATE from the M=1 kernel deliberately: the single-row kernel's
    (1, B)/(1, D) lane-major layouts are the measured-fastest formulation
    for the headline solve (see the module table — round 1's alternative
    layouts lost 1.0-2.6x), and routing M=1 through this kernel's (M, ·)
    shapes was not measured equal. Any change to the block-selection /
    padding / BlockSpec plumbing here must be mirrored in
    :func:`fused_value_and_grad` (and vice versa)."""
    n, d = x.shape
    n_lanes = ws.shape[0]
    tile = _sublane_tile(x.dtype)
    if block_rows is None:
        b = auto_block_rows(n, x.dtype)
        if b is None:
            b = _rounded_block(n, _default_block_rows(x.dtype), tile)
    else:
        b = _rounded_block(n, block_rows, tile)
    n_blocks = pl.cdiv(n, b)
    n_pad = n_blocks * b
    if n_pad != n:
        pad = n_pad - n
        x = jnp.pad(x, ((0, pad), (0, 0)))
        labels = jnp.pad(labels, (0, pad))
        offsets = jnp.pad(offsets, (0, pad))
        weights = jnp.pad(weights, (0, pad))

    f32 = jnp.float32
    itemsize = jnp.dtype(x.dtype).itemsize
    out = pl.pallas_call(
        functools.partial(_kernel_multi, loss),
        grid=(n_blocks,),
        in_specs=[
            pl.BlockSpec((b, d), lambda i: (i, 0), memory_space=pltpu.VMEM),
            pl.BlockSpec((1, 1, b), lambda i: (i, 0, 0), memory_space=pltpu.VMEM),
            pl.BlockSpec((1, 1, b), lambda i: (i, 0, 0), memory_space=pltpu.VMEM),
            pl.BlockSpec((1, 1, b), lambda i: (i, 0, 0), memory_space=pltpu.VMEM),
            pl.BlockSpec((n_lanes, d), lambda i: (0, 0),
                         memory_space=pltpu.VMEM),
        ],
        out_specs=[
            pl.BlockSpec((1, n_lanes), lambda i: (0, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((n_lanes, d), lambda i: (0, 0),
                         memory_space=pltpu.VMEM),
        ],
        out_shape=[
            _out_struct(x, (1, n_lanes), f32),
            _out_struct(x, (n_lanes, d), f32),
        ],
        cost_estimate=pl.CostEstimate(
            flops=4 * n_pad * d * n_lanes,
            transcendentals=2 * n_pad * n_lanes,
            bytes_accessed=n_pad * d * itemsize,
        ),
        interpret=interpret,
    )(
        x,
        labels.astype(f32).reshape(n_blocks, 1, b),
        offsets.astype(f32).reshape(n_blocks, 1, b),
        weights.astype(f32).reshape(n_blocks, 1, b),
        ws.astype(f32),
    )
    value, grad = out
    return value[0, :], grad


@functools.lru_cache(maxsize=None)
def vmappable_value_and_grad(loss: PointwiseLoss, interpret: bool = False):
    """The fused (value, grad) with a custom vmap rule: a vmap over the
    coefficient vector alone (the batched lambda sweep) dispatches to the
    multi-row kernel — one pass over X shared by all lanes, M margins as M
    rows of one MXU matmul — instead of M independent kernel passes. Any
    other batching combination falls back to a sequential lane map."""

    @jax.custom_batching.custom_vmap
    def vag(x, w, labels, offsets, weights):
        return fused_value_and_grad(loss, x, w, labels, offsets, weights,
                                    interpret=interpret)

    @vag.def_vmap
    def _rule(axis_size, in_batched, x, w, labels, offsets, weights):
        xb, wb, lb, ob, wtb = in_batched
        if wb and not (xb or lb or ob or wtb):
            values, grads = fused_value_and_grad_multi(
                loss, x, w, labels, offsets, weights, interpret=interpret)
            return (values, grads), (True, True)

        def body(i):
            return fused_value_and_grad(
                loss, x[i] if xb else x, w[i] if wb else w,
                labels[i] if lb else labels, offsets[i] if ob else offsets,
                weights[i] if wtb else weights, interpret=interpret)

        values, grads = jax.lax.map(body, jnp.arange(axis_size))
        return (values, grads), (True, True)

    return vag


def _hvp_kernel(x_ref, d2_ref, v_ref, out_ref):
    """One-pass GLM Hessian-vector product: out = Xᵀ(d2 ∘ (Xv)).

    Same lane-major shape discipline as :func:`_kernel` — both
    contractions are 1-row matmuls against the SAME resident x block, so
    the design streams through VMEM exactly once per product (the XLA
    closed form reads it twice: matvec then rmatvec). ``d2`` is the
    precomputed per-sample weight·d2loss vector — margin-dependent only
    through ``w``, so TRON's inner CG (many products at fixed ``w``)
    amortizes its computation to zero.
    """
    i = pl.program_id(0)

    @pl.when(i == 0)
    def _init():
        out_ref[:] = jnp.zeros_like(out_ref)

    x = x_ref[:]  # (B, D)
    v = v_ref[:]  # (1, D) f32
    d2 = d2_ref[0]  # (1, B)
    precision = (jax.lax.Precision.HIGHEST if x.dtype == jnp.float32
                 else jax.lax.Precision.DEFAULT)
    t = jax.lax.dot_general(
        v.astype(x.dtype), x,
        dimension_numbers=(((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32,
        precision=precision)  # (1, B) = (Xv)ᵀ for this block
    out_ref[:] += jax.lax.dot_general(
        (d2 * t).astype(x.dtype), x,
        dimension_numbers=(((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
        precision=precision)  # (1, D)


@functools.partial(jax.jit, static_argnames=("block_rows", "interpret"))
def fused_hvp(x, v, d2w, *, block_rows: int | None = None,
              interpret: bool = False):
    """``Xᵀ(d2w ∘ (Xv))`` in ONE pass over ``x`` (no L2 term — caller adds).

    ``x`` is ``(n, d)``; ``v`` ``(d,)`` f32; ``d2w`` ``(n,)`` the
    weight-and-padding-masked second derivatives (0 on padded rows, which
    then contribute exactly nothing). Block selection mirrors
    :func:`fused_value_and_grad` via the shared :func:`auto_block_rows`.
    """
    n, d = x.shape
    tile = _sublane_tile(x.dtype)
    if block_rows is None:
        b = auto_block_rows(n, x.dtype)
        if b is None:  # no dividing block: padding path
            b = _rounded_block(n, _default_block_rows(x.dtype), tile)
    else:
        b = _rounded_block(n, block_rows, tile)
    n_blocks = pl.cdiv(n, b)
    n_pad = n_blocks * b
    if n_pad != n:
        x = jnp.pad(x, ((0, n_pad - n), (0, 0)))
        d2w = jnp.pad(d2w, (0, n_pad - n))

    f32 = jnp.float32
    itemsize = jnp.dtype(x.dtype).itemsize
    out = pl.pallas_call(
        _hvp_kernel,
        # the operation's name in a profiler trace, as the kernel above has
        # its own (benchmark/metrics/hvp_kernel_roofline_pct.json)
        name="fused_hvp",
        grid=(n_blocks,),
        in_specs=[
            pl.BlockSpec((b, d), lambda i: (i, 0), memory_space=pltpu.VMEM),
            pl.BlockSpec((1, 1, b), lambda i: (i, 0, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, d), lambda i: (0, 0), memory_space=pltpu.VMEM),
        ],
        out_specs=pl.BlockSpec((1, d), lambda i: (0, 0),
                               memory_space=pltpu.VMEM),
        out_shape=_out_struct(x, (1, d), f32),
        cost_estimate=pl.CostEstimate(
            flops=4 * n_pad * d,
            transcendentals=0,
            bytes_accessed=n_pad * d * itemsize,
        ),
        interpret=interpret,
    )(
        x,
        d2w.astype(f32).reshape(n_blocks, 1, b),
        v.astype(f32).reshape(1, -1),
    )
    return out[0, :]
