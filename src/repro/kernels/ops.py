"""Kernel dispatch layer: TPU -> Pallas, CPU/dry-run -> jnp reference.

Models call these entry points only; the backend choice is per-call overridable
(`impl=`) and defaults to the platform: the Mosaic kernels on TPU, the
FLOP-equivalent jnp paths everywhere else (including the 512-fake-device CPU
dry-run, which cannot lower TPU Pallas). `interpret=True` Pallas execution is
reserved for the correctness tests.

The sequence kernels (flash attention, the mamba2 and rwkv6 scans) have no
backward kernels yet: their Pallas forwards are differentiated through the
VJP of the matching `ref` function, recomputed from the saved inputs. XLA
cannot partition a Mosaic kernel, so under a multi-device mesh they run per
device in a shard_map.
"""
from __future__ import annotations

import functools
import logging
from typing import Callable, Optional

import jax
from jax.sharding import PartitionSpec as P

from repro.kernels import ref

log = logging.getLogger("repro.kernels")
_FORCED_IMPL: Optional[str] = None  # test hook: "jnp" | "pallas" | "pallas_interpret"


def set_default_impl(impl: Optional[str]) -> None:
    global _FORCED_IMPL
    _FORCED_IMPL = impl


def _resolve(impl: Optional[str]) -> str:
    if impl is not None:
        return impl
    if _FORCED_IMPL is not None:
        return _FORCED_IMPL
    platform = jax.default_backend()
    return "pallas" if platform == "tpu" else "jnp"


def _with_ref_vjp(kernel: Callable, reference: Callable) -> Callable:
    """`kernel` forward; backward is `reference`'s VJP at the same inputs."""
    @jax.custom_vjp
    def f(*args):
        return kernel(*args)

    def fwd(*args):
        return kernel(*args), args

    def bwd(args, ct):
        return jax.vjp(reference, *args)[1](ct)

    f.defvjp(fwd, bwd)
    return f


def _sequence_kernel(kernel: Callable, reference: Callable, args: tuple,
                     in_dims: tuple, out_dims):
    """Pallas `kernel` with `reference`'s VJP, per device under a mesh.

    `in_dims`/`out_dims` name each array dim with a logical axis of
    `models.partitioning` ("batch", "model") or None; `out_dims` is a list
    for several outputs. Under a multi-device ambient mesh each logical axis
    splits over its mesh axes where every dim of that name divides them, and
    is otherwise replicated (each device then repeats that work; logged).
    """
    from repro.models.partitioning import fit_axes

    f = _with_ref_vjp(kernel, reference)
    mesh = jax.sharding.get_abstract_mesh()
    if mesh.empty or mesh.size == 1:
        return f(*args)
    use = {}
    for name in {d for dims in in_dims for d in dims if d is not None}:
        sizes = [x.shape[i] for x, dims in zip(args, in_dims)
                 for i, d in enumerate(dims) if d == name]
        use[name] = fit_axes(mesh, name, sizes)
        if use[name] is None:
            log.warning("%s: %r dims %s do not split over mesh %s; replicated",
                        getattr(kernel, "func", kernel).__name__, name, sizes,
                        dict(mesh.shape))

    def spec(dims):
        return P(*(use.get(d) for d in dims))

    out_specs = (tuple(map(spec, out_dims)) if isinstance(out_dims, list)
                 else spec(out_dims))
    return jax.shard_map(f, mesh=mesh, in_specs=tuple(map(spec, in_dims)),
                         out_specs=out_specs, check_vma=False)(*args)


_B, _H = "batch", "model"    # heads split over the tensor-parallel axis
_BSHD = (_B, None, _H, None)   # (B, S, H, d) activations


def flash_attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
                    causal: bool = True, window: Optional[int] = None,
                    impl: Optional[str] = None) -> jax.Array:
    """Blocked attention. q (B,Sq,H,hd); k/v (B,Sk,K,hd) with GQA K<=H."""
    mode = _resolve(impl)
    if mode == "jnp":
        return ref.flash_attention_jnp(q, k, v, causal=causal, window=window)
    from repro.kernels import flash_attention as fa
    return _sequence_kernel(
        functools.partial(fa.flash_attention, causal=causal, window=window,
                          interpret=(mode == "pallas_interpret")),
        functools.partial(ref.flash_attention_jnp, causal=causal,
                          window=window),
        (q, k, v), (_BSHD,) * 3, _BSHD)


def decode_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                     valid_len: jax.Array, *, window: Optional[int] = None,
                     impl: Optional[str] = None) -> jax.Array:
    """One-token attention over a KV cache (flash-decode combine under pjit)."""
    mode = _resolve(impl)
    # decode is bandwidth-bound and already lowers to partial-reduce + psum on
    # sharded caches; the jnp path is used on all platforms unless profiling
    # shows a kernel win (EXPERIMENTS §Perf).
    del mode
    return ref.decode_attention_jnp(q, k, v, valid_len, window=window)


def mamba2_mix(x: jax.Array, dt: jax.Array, a: jax.Array, b: jax.Array,
               c: jax.Array, d: jax.Array, *, chunk: int = 128,
               init_state: Optional[jax.Array] = None,
               impl: Optional[str] = None) -> tuple[jax.Array, jax.Array]:
    """Mamba2/SSD sequence mixing. Returns (y, final_state)."""
    mode = _resolve(impl)
    if mode == "jnp" or init_state is not None:   # the kernel starts at zero
        return ref.mamba2_chunked_jnp(x, dt, a, b, c, d, chunk=chunk,
                                      init_state=init_state)
    from repro.kernels import mamba2_scan as m2
    # one group (the common case) is shared by every head: replicate it
    groups = (_B, None, _H if b.shape[2] > 1 else None, None)
    return _sequence_kernel(
        functools.partial(m2.mamba2_chunked, chunk=chunk,
                          interpret=(mode == "pallas_interpret")),
        functools.partial(ref.mamba2_chunked_jnp, chunk=chunk),
        (x, dt, a, b, c, d),
        (_BSHD, (_B, None, _H), (_H,), groups, groups, (_H,)),
        [_BSHD, (_B, _H, None, None)])


def mamba2_decode_step(x: jax.Array, dt: jax.Array, a: jax.Array, b: jax.Array,
                       c: jax.Array, d: jax.Array,
                       state: jax.Array) -> tuple[jax.Array, jax.Array]:
    """Single-token SSD update (serving): state (B,H,P,N)."""
    y, new_state = ref.mamba2_scan_ref(x, dt, a, b, c, d, init_state=state)
    return y, new_state


def rwkv6_mix(r: jax.Array, k: jax.Array, v: jax.Array, w: jax.Array,
              u: jax.Array, *, init_state: Optional[jax.Array] = None,
              impl: Optional[str] = None) -> tuple[jax.Array, jax.Array]:
    """RWKV6 wkv recurrence. Returns (y, final_state)."""
    mode = _resolve(impl)
    if mode == "jnp" or init_state is not None:   # the kernel starts at zero
        return ref.rwkv6_scan_ref(r, k, v, w, u, init_state=init_state)
    from repro.kernels import rwkv6_scan as r6
    return _sequence_kernel(
        functools.partial(r6.rwkv6_chunked,
                          interpret=(mode == "pallas_interpret")),
        ref.rwkv6_scan_ref, (r, k, v, w, u),
        (_BSHD,) * 4 + ((_H, None),), [_BSHD, (_B, _H, None, None)])


def sam_perturb(w_flat: jax.Array, g_flat: jax.Array, rho, sq_norm, *,
                impl: Optional[str] = None) -> jax.Array:
    """Fused  w + rho * g / ||g||  over a flat fp32 vector."""
    mode = _resolve(impl)
    if mode == "jnp":
        return ref.sam_perturb_flat_jnp(w_flat, g_flat, rho, sq_norm)
    from repro.kernels import sam_perturb as sp
    return sp.sam_perturb(w_flat, g_flat, rho, sq_norm,
                          interpret=(mode == "pallas_interpret"))


def sq_norm(g_flat: jax.Array, *, impl: Optional[str] = None) -> jax.Array:
    """Sum of squares of a flat vector (fp32 chunk partials on TPU)."""
    mode = _resolve(impl)
    if mode == "jnp":
        return ref.sq_norm_jnp(g_flat)
    from repro.kernels import sam_perturb as sp
    return sp.sq_norm(g_flat, interpret=(mode == "pallas_interpret"))


def fused_axpy(alpha, x_flat: jax.Array, y_flat: jax.Array, *,
               impl: Optional[str] = None) -> jax.Array:
    """Single-pass  y + alpha * x  over flat vectors (y's dtype out)."""
    mode = _resolve(impl)
    if mode == "jnp":
        return ref.axpy_flat_jnp(alpha, x_flat, y_flat)
    from repro.kernels import fused_update as fu
    return fu.fused_axpy(alpha, x_flat, y_flat,
                         interpret=(mode == "pallas_interpret"))


def fused_dot_norms(a_flat: jax.Array, b_flat: jax.Array, *,
                    impl: Optional[str] = None
                    ) -> tuple[jax.Array, jax.Array, jax.Array]:
    """(<a,b>, ||a||^2, ||b||^2) in one pass over (a, b)."""
    mode = _resolve(impl)
    if mode == "jnp":
        return ref.dot_norms_flat_jnp(a_flat, b_flat)
    from repro.kernels import fused_update as fu
    return fu.fused_dot_norms(a_flat, b_flat,
                              interpret=(mode == "pallas_interpret"))


def delta_amax(p_flat: jax.Array, s_flat: jax.Array, e_flat: jax.Array, *,
               impl: Optional[str] = None) -> jax.Array:
    """max |p - s + e| over flat buckets (JOB-delta int8 scale probe)."""
    mode = _resolve(impl)
    if mode == "jnp":
        return ref.delta_amax_flat_jnp(p_flat, s_flat, e_flat)
    from repro.kernels import fused_update as fu
    return fu.delta_amax(p_flat, s_flat, e_flat,
                         interpret=(mode == "pallas_interpret"))


def delta_encode_i8(p_flat: jax.Array, s_flat: jax.Array, e_flat: jax.Array,
                    scale, *, impl: Optional[str] = None
                    ) -> tuple[jax.Array, jax.Array, jax.Array]:
    """One-pass int8 delta encode: (q int8, shadow' fp32, residual' fp32)."""
    mode = _resolve(impl)
    if mode == "jnp":
        return ref.delta_encode_i8_flat_jnp(p_flat, s_flat, e_flat, scale)
    from repro.kernels import fused_update as fu
    return fu.delta_encode_i8(p_flat, s_flat, e_flat, scale,
                              interpret=(mode == "pallas_interpret"))


def sgd_epilogue(w_flat: jax.Array, g_flat: jax.Array, m_flat, clip_scale, lr,
                 *, momentum: float = 0.0, nesterov: bool = False,
                 weight_decay: float = 0.0, impl: Optional[str] = None):
    """Fused clip-wd-momentum-lr-apply (SGD family): (w', m'-or-None)."""
    mode = _resolve(impl)
    if mode == "jnp":
        return ref.sgd_epilogue_flat_jnp(w_flat, g_flat, m_flat, clip_scale,
                                         lr, momentum=momentum,
                                         nesterov=nesterov,
                                         weight_decay=weight_decay)
    from repro.kernels import fused_update as fu
    return fu.sgd_epilogue(w_flat, g_flat, m_flat, clip_scale, lr,
                           momentum=momentum, nesterov=nesterov,
                           weight_decay=weight_decay,
                           interpret=(mode == "pallas_interpret"))


def adamw_epilogue(w_flat: jax.Array, g_flat: jax.Array, mu_flat: jax.Array,
                   nu_flat: jax.Array, clip_scale, lr, c1, c2, *,
                   b1: float = 0.9, b2: float = 0.999, eps: float = 1e-8,
                   weight_decay: float = 0.0, impl: Optional[str] = None):
    """Fused clip-adam-wd-lr-apply (AdamW family): (w', mu', nu')."""
    mode = _resolve(impl)
    if mode == "jnp":
        return ref.adamw_epilogue_flat_jnp(w_flat, g_flat, mu_flat, nu_flat,
                                           clip_scale, lr, c1, c2, b1=b1,
                                           b2=b2, eps=eps,
                                           weight_decay=weight_decay)
    from repro.kernels import fused_update as fu
    return fu.adamw_epilogue(w_flat, g_flat, mu_flat, nu_flat, clip_scale, lr,
                             c1, c2, b1=b1, b2=b2, eps=eps,
                             weight_decay=weight_decay,
                             interpret=(mode == "pallas_interpret"))
