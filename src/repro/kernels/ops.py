"""Public kernel entry points.

``gram_and_rhs`` / ``sddmm`` / ``topk_score`` dispatch between the
Pallas kernel (compiled on TPU, interpreted on CPU) and the pure-jnp
oracle, controlled by the ``use_pallas`` flag carried in the session
config.  The default is the XLA path; the tests exercise the Pallas
path in interpret mode on CPU, ``tests/test_tpu_compile.py`` compiles
it for a described TPU v5e, and ``chip_smoke.py`` runs it on the chip.

The factor rows' Cholesky draw (``draw_branches``) is chosen by
platform and shape, not by ``use_pallas``: on a TPU it runs the
``chol_draw`` kernel, since XLA's batched Cholesky and triangular
inverse there take one matrix at a time and are no real alternative;
every other backend keeps ``lax.linalg`` (LAPACK on the CPU).  A K
above ``CHOL_DRAW_MAX_K`` takes ``lax.linalg`` everywhere.  The choice
is made per traced program; ``repro.obs.trace_counts()`` counts it as
``solve.kernel_draws`` / ``solve.lax_draws``.  No option selects it.

Kernel contracts
----------------
Every kernel shipped from this package is registered in ``KERNELS``
below and statically verified — grid race-freedom, block bounds over
the shared padding path, fp32 accumulation, and a per-grid-step VMEM
budget — by ``repro.analysis.kernelcheck`` (CI: ``python -m
repro.analysis --kernels``; rule catalogue in
``src/repro/analysis/README.md``).  The registry's probes are the
supported shape envelope: the checker concretely enumerates each
kernel's grid over exactly these configurations, so a new kernel, a
new block size, or a bigger serving store belongs in a new
:class:`KernelProbe` **first** — interpret mode on CPU does not check
grids, and the checker is what stands between a grid bug and a TPU
run.  All wrapper padding goes through :func:`pad_to_blocks` so the
bounds checker verifies a single padding path.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any, Callable, Mapping, Tuple

import jax
import jax.numpy as jnp

from ..obs import count_trace
from . import ref
from .chol_draw import LANES, chol_draw_pallas
from .flash import flash_fwd_pallas
from .gram import gram_pallas
from .sddmm import sddmm_pallas
from .topk_score import topk_score_pallas


def _interpret(interpret: bool | None) -> bool:
    """Whether a Pallas call runs in interpret mode.

    An explicit ``interpret`` wins.  Otherwise it is decided at call
    time from the backend: the TPU compiles the kernel, the CPU
    interprets it, and any other backend raises instead of silently
    running the interpreter on a device.
    """
    if interpret is not None:
        return interpret
    backend = jax.default_backend()
    if backend == "tpu":
        return False
    if backend == "cpu":
        return True
    raise RuntimeError(
        f"no Pallas path for backend {backend!r}: the kernels compile "
        "for TPU and run in interpret mode on CPU only")

# the serving hot loop calls this per service step; eager lax.map
# dispatch costs more than the scoring itself (measured ~100x on the
# quick latency benchmark)
_topk_ref_jit = functools.partial(jax.jit, static_argnums=(3,))(
    ref.topk_score_ref)


def pad_to_blocks(x: jnp.ndarray,
                  multiples: Mapping[int, int]) -> jnp.ndarray:
    """Pad the trailing edge of the given axes of ``x`` up to the next
    multiple of each block size (zero fill).

    ``multiples`` maps axis -> block multiple.  Returns ``x`` itself
    when every axis is already aligned, so the aligned fast path adds
    no ops.  This is the ONE padding path every Pallas wrapper uses;
    ``repro.analysis.kernelcheck`` verifies the resulting grids stay
    in bounds for uneven tails, so new wrappers must route their
    padding through here too.
    """
    widths = [(0, 0)] * x.ndim
    need = False
    for ax, mult in multiples.items():
        if mult < 1:
            raise ValueError(
                f"block multiple for axis {ax} must be >= 1, got {mult}")
        pad = (-x.shape[ax]) % mult
        widths[ax] = (0, pad)
        need = need or pad > 0
    return jnp.pad(x, widths) if need else x


def gram_and_rhs(vg: jnp.ndarray, val: jnp.ndarray, mask: jnp.ndarray,
                 *, use_pallas: bool = False, interpret: bool | None = None):
    """Fused masked batched Gram; see kernels/gram.py.

    Pads rows/nnz up to the kernel block multiples (mask-0 padding is an
    exact no-op) and slices the result back.
    """
    if not use_pallas:
        return ref.gram_ref(vg, val, mask)
    interpret = _interpret(interpret)
    # a block as wide as T is always legal; wider rows tile by 128
    br, bt = 8, min(128, vg.shape[1])
    R = vg.shape[0]
    vg_p = pad_to_blocks(vg, {0: br, 1: bt})
    val_p = pad_to_blocks(val, {0: br, 1: bt})
    mask_p = pad_to_blocks(mask, {0: br, 1: bt})
    gram, rhs = gram_pallas(vg_p, val_p, mask_p, block_rows=br,
                            block_nnz=bt, interpret=interpret)
    return gram[:R], rhs[:R]


def topk_score(us: jnp.ndarray, v: jnp.ndarray, k: int, *,
               exclude: jnp.ndarray | None = None,
               use_pallas: bool = False,
               interpret: bool | None = None):
    """Batched posterior scoring + top-K; see kernels/topk_score.py.

    us (B, S, K) user rows per sample, v (S, N, K) item factor stack,
    ``exclude`` (B, N) truthy = leave out of the ranking ->
    (ids (B, k') i32, mean (B, k') f32, std (B, k') f32) with
    k' = min(k, N).  Slots past the number of rankable (non-excluded)
    items of a row carry id -1 and NaN mean/std — identically on both
    the kernel and the reference path, so K > n_items clamps instead
    of surfacing padding artifacts.

    Both paths see the SAME item-padded operands (pad items carry
    exclude 1.0, an exact ranking no-op) and the std is finalized here
    from the selected (mean, ex2): shape-dependent vectorization would
    otherwise drift the two paths by 1 ulp (measured), and the serving
    contract is fp32 BITWISE kernel == reference.
    """
    if k < 1:
        raise ValueError(f"k must be >= 1, got {k}")
    B, S, K = us.shape
    N = v.shape[1]
    k_eff = min(int(k), N)
    if exclude is None:
        excl = jnp.zeros((B, N), jnp.float32)
    else:
        excl = jnp.asarray(exclude)
        if excl.shape != (B, N):
            raise ValueError(
                f"exclude shape {excl.shape} != (B, N) = {(B, N)}")
        excl = (excl > 0).astype(jnp.float32)

    # the (S, bn, K) item tile is double-buffered in VMEM with K padded
    # to 128 lanes; 256 items per tile unless that tile passes 4 MiB
    lanes = -(-K // 128) * 128
    bn = 256 if S * 256 * lanes * 4 <= 4 << 20 else 128
    v_p = pad_to_blocks(v, {1: bn})
    pad = v_p.shape[1] - N
    # padded items are excluded so they can never be selected
    excl_p = jnp.pad(excl, ((0, 0), (0, pad)), constant_values=1.0)

    if not use_pallas:
        ids, mean, ex2 = _topk_ref_jit(us, v_p, excl_p, k_eff)
    else:
        interpret = _interpret(interpret)
        ids, mean, ex2, _ = topk_score_pallas(
            us, v_p, excl_p, k=k_eff, block_items=bn,
            interpret=interpret)

    std = jnp.sqrt(jnp.maximum(ex2 - mean * mean, 0.0))
    # rows with fewer than k_eff rankable items: invalidate the tail
    n_valid = jnp.sum(excl <= 0, axis=1).astype(jnp.int32)   # (B,)
    slot = jnp.arange(k_eff, dtype=jnp.int32)[None, :]
    bad = slot >= n_valid[:, None]
    ids = jnp.where(bad, -1, ids)
    mean = jnp.where(bad, jnp.nan, mean)
    std = jnp.where(bad, jnp.nan, std)
    return ids, mean, std


def sddmm(ug: jnp.ndarray, vg: jnp.ndarray, *, use_pallas: bool = False,
          interpret: bool | None = None) -> jnp.ndarray:
    """Gathered-operand SDDMM; see kernels/sddmm.py."""
    if not use_pallas:
        return ref.sddmm_ref(ug, vg)
    interpret = _interpret(interpret)
    be, bk = 1024, 128
    E = ug.shape[0]
    ug_p = pad_to_blocks(ug, {0: be, 1: bk})
    vg_p = pad_to_blocks(vg, {0: be, 1: bk})
    out = sddmm_pallas(ug_p, vg_p, block_e=be, block_k=bk,
                       interpret=interpret)
    return out[:E]


# the largest K the draw kernel takes: its (K, K, 128) float32
# precisions and the double-buffered (128 K, 128) Gram columns are
# 65 MiB at K=256, about half of a v5e core's 128 MiB of VMEM; the next
# width, 384, would need 120 MiB
CHOL_DRAW_MAX_K = 256


def chol_draw(gram_rows: jnp.ndarray, lam: jnp.ndarray, b: jnp.ndarray,
              z: jnp.ndarray, *, interpret: bool | None = None):
    """Lane-batched conditional draw; see kernels/chol_draw.py.

    gram_rows (N, K, K), lam (K, K) added to every row's precision, b
    and z (N, K) -> u (N, K) with u_n = Lambda_n^{-1} b_n + R_n^{-1} z_n,
    Lambda_n = gram_rows_n + lam = R_n^T R_n: ``ref.chol_draw_ref``'s
    mathematics.  The kernel reads the Gram rows as they lie and puts
    the batch on the lanes itself.  K pads to a multiple of 8, or of
    128 above 128 (zero Gram, identity in lam), and N to a multiple of
    128 (zero Gram over the positive definite lam), b and z with zeros:
    the padding solves to exact zeros and is sliced off.
    """
    interpret = _interpret(interpret)
    N, K = b.shape
    # K past one lane width goes over the kernel's column steps
    mult = 8 if K <= LANES else LANES
    gram = pad_to_blocks(gram_rows, {0: LANES, 1: mult, 2: mult})
    n_p, kp = gram.shape[:2]
    lam_p = pad_to_blocks(lam, {0: mult, 1: mult})
    if kp != K:
        pad = jnp.arange(kp) >= K
        lam_p = lam_p + jnp.diag(pad.astype(lam_p.dtype))
    bt = pad_to_blocks(b.T, {0: mult, 1: LANES})
    zt = pad_to_blocks(z.T, {0: mult, 1: LANES})
    u = chol_draw_pallas(gram.reshape(n_p * kp, kp), lam_p, bt, zt,
                         interpret=interpret)
    return u[:K, :N].T


def _draw_on_tpu(gram_rows, lam, b, z):
    return chol_draw(gram_rows, lam, b, z, interpret=False)


# every backend but the TPU; a test that must run the kernel's branch
# on the CPU points this at ``chol_draw`` in interpret mode
_draw_elsewhere = ref.chol_draw_ref


def draw_branches(k: int):
    """The conditional draw's ``(tpu, default)`` branches for
    ``jax.lax.platform_dependent`` at latent width ``k``, each taking
    ``(gram_rows, lam, b, z)`` as ``chol_draw`` does.

    Up to ``CHOL_DRAW_MAX_K`` the ``chol_draw`` kernel on a TPU and
    ``lax.linalg`` elsewhere (module docstring); above it ``lax.linalg``
    on both.  Each call counts one traced draw by the decision.
    """
    if k > CHOL_DRAW_MAX_K:
        count_trace("solve.lax_draws")
        return ref.chol_draw_ref, ref.chol_draw_ref
    count_trace("solve.kernel_draws")
    return _draw_on_tpu, _draw_elsewhere


# ---------------------------------------------------------------------------
# kernel registry: the statically-verified shape envelope
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class KernelProbe:
    """One concrete configuration the checker enumerates.

    ``call(*arrays)`` must drive the public wrapper with the Pallas
    path forced (so the wrapper's padding arithmetic is part of what
    gets verified); ``args`` are ``jax.ShapeDtypeStruct`` operands —
    the probe is traced with ``jax.eval_shape``, never executed.
    """
    label: str
    args: Tuple[Any, ...]
    call: Callable[..., Any]


@dataclasses.dataclass(frozen=True)
class KernelSpec:
    """Registry entry for one shipped Pallas kernel."""
    name: str
    probes: Tuple[KernelProbe, ...]
    vmem_budget: int                 # per-grid-step resident bytes
    jit_fns: Tuple[Any, ...] = ()    # jitted entries to cache-clear
    #                                  around capture (see kernelcheck)


def _sds(shape, dtype=jnp.float32):
    return jax.ShapeDtypeStruct(shape, dtype)


def _gram_call(vg, val, mask):
    return gram_and_rhs(vg, val, mask, use_pallas=True)


def _sddmm_call(ug, vg):
    return sddmm(ug, vg, use_pallas=True)


def _topk_call(k):
    def call(us, v):
        return topk_score(us, v, k, use_pallas=True)
    return call


def _topk_call_excl(k):
    def call(us, v, ex):
        return topk_score(us, v, k, exclude=ex, use_pallas=True)
    return call


def _chol_draw_call(gram_rows, lam, b, z):
    return chol_draw(gram_rows, lam, b, z)


def _chol_draw_probe(label, n, k):
    return KernelProbe(label, (_sds((n, k, k)), _sds((k, k)),
                               _sds((n, k)), _sds((n, k))),
                       _chol_draw_call)


def _flash_call(**kw):
    def call(q, k, v):
        return flash_fwd_pallas(q, k, v, **kw)
    return call


_BF16 = jnp.bfloat16

KERNELS = {
    "gram": KernelSpec(
        "gram",
        probes=(
            KernelProbe("production r64 t256 K128",
                        (_sds((64, 256, 128)), _sds((64, 256)),
                         _sds((64, 256))), _gram_call),
            KernelProbe("uneven tail r13 t257 K33",
                        (_sds((13, 257, 33)), _sds((13, 257)),
                         _sds((13, 257))), _gram_call),
            KernelProbe("bf16 gathered operands",
                        (_sds((16, 130, 32), _BF16),
                         _sds((16, 130), _BF16),
                         _sds((16, 130), _BF16)), _gram_call),
        ),
        vmem_budget=4 << 20,
        jit_fns=(gram_pallas,)),
    "sddmm": KernelSpec(
        "sddmm",
        probes=(
            KernelProbe("production e4096 K128",
                        (_sds((4096, 128)), _sds((4096, 128))),
                        _sddmm_call),
            KernelProbe("uneven tail e1025 K200",
                        (_sds((1025, 200)), _sds((1025, 200))),
                        _sddmm_call),
        ),
        # the 1024-row block matches XLA's TPU tiling of the 1-D
        # output; two double-buffered (1024, 128) f32 operands
        vmem_budget=4 << 20,
        jit_fns=(sddmm_pallas,)),
    "topk_score": KernelSpec(
        "topk_score",
        probes=(
            KernelProbe("serving b8 s32 n4096 K32 k100",
                        (_sds((8, 32, 32)), _sds((32, 4096, 32))),
                        _topk_call(100)),
            KernelProbe("catalogue b4 s64 n2048 K64 k100",
                        (_sds((4, 64, 64)), _sds((64, 2048, 64))),
                        _topk_call(100)),
            KernelProbe("uneven tail + exclusions b3 s8 n130 k7",
                        (_sds((3, 8, 16)), _sds((8, 130, 16)),
                         _sds((3, 130))), _topk_call_excl(7)),
        ),
        vmem_budget=12 << 20,
        jit_fns=(topk_score_pallas,)),
    "chol_draw": KernelSpec(
        "chol_draw",
        probes=(
            _chol_draw_probe("production rows n32768 K128", 32_768, 128),
            _chol_draw_probe("production cols n8192 K128", 8_192, 128),
            _chol_draw_probe("uneven tail n300 K13", 300, 13),
            _chol_draw_probe("widest, two column steps n256 K256",
                             256, 256),
        ),
        # the double-buffered (128 K, 128) Gram columns and the
        # (K, K, 128) precisions: 24.5 MiB at K=128, 65 MiB at K=256
        vmem_budget=66 << 20),
    "flash": KernelSpec(
        "flash",
        probes=(
            KernelProbe("causal GQA b2 s256 h4/2 hd128",
                        (_sds((2, 256, 4, 128)), _sds((2, 256, 2, 128)),
                         _sds((2, 256, 2, 128))),
                        _flash_call(causal=True)),
            KernelProbe("windowed decode offset s64 vs 256",
                        (_sds((1, 64, 4, 16)), _sds((1, 256, 2, 16)),
                         _sds((1, 256, 2, 16))),
                        _flash_call(causal=True, window=128,
                                    q_offset=192)),
            KernelProbe("noncausal bf16 uneven s130",
                        (_sds((1, 130, 2, 8), _BF16),
                         _sds((1, 130, 1, 8), _BF16),
                         _sds((1, 130, 1, 8), _BF16)),
                        _flash_call(causal=False)),
        ),
        vmem_budget=4 << 20,
        jit_fns=(flash_fwd_pallas,)),
}
