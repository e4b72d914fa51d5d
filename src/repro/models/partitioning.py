"""Activation + parameter sharding with logical axis names.

Models call `constrain(x, ("batch", None, "model"))` at layer boundaries and
`constrain_param_tree(blk)` on scanned per-layer parameter slices; launchers
opt in with `activation_sharding(mesh)` which maps the logical axes onto mesh
axes ("batch" -> the dp axes, "model" -> the TP axis). Without an active
mapping (unit tests, single-device runs) everything is a no-op, so model code
stays mesh-agnostic.

`constrain_param_tree` exists for a specific pod-scale reason: with
scan-over-layers + FSDP, XLA's loop-invariant code motion hoists the weight
all-gather of the *stacked* (n_layers, ...) parameters out of the loop,
materializing every layer's gathered weights at once (observed 300+GB/device
on qwen2.5-32b). Re-constraining the per-layer slice inside the body makes the
gather depend on the loop index, forcing per-layer gathers — ZeRO-3 semantics.

The parameter rules live here (not in launch/) so both the model bodies and
the launcher-side `launch.sharding` derive specs from one table.
"""
from __future__ import annotations

import contextlib
from typing import Any, Optional, Sequence

import jax
from jax.sharding import PartitionSpec as P

Pytree = Any
_RULES: Optional[dict] = None


def make_rules(mesh) -> dict:
    from repro.launch.mesh import dp_axes

    dp = dp_axes(mesh)
    model = ("model",)

    def size(axes):
        n = 1
        for a in axes:
            n *= mesh.shape[a]
        return n

    pod = ("pod",) if "pod" in mesh.axis_names else ()
    return {
        "batch": (dp, size(dp)),
        "model": (model, size(model)),
        "batch_model": (dp + model, size(dp + model)),
        # expert dim: span pods too so EP groups do not replicate per pod
        "pod_model": (pod + model, size(pod + model)),
        "data_only": (("data",), size(("data",))),
    }


def fit_axes(mesh, logical: str, dim_sizes: Sequence[int]
             ) -> Optional[tuple[str, ...]]:
    """Mesh axes of `logical` on `mesh` if they split every one of
    `dim_sizes` evenly, else None (replicated) — `constrain`'s rule, for code
    that places arrays itself (the per-device Pallas kernels)."""
    axes, n = make_rules(mesh)[logical]
    return axes if all(s % n == 0 for s in dim_sizes) else None


@contextlib.contextmanager
def activation_sharding(mesh):
    """Enable logical-axis constraints for code traced inside this context."""
    global _RULES
    prev = _RULES
    _RULES = make_rules(mesh)
    try:
        yield
    finally:
        _RULES = prev


def constrain(x: jax.Array, dims: Sequence[Optional[str]]) -> jax.Array:
    """Apply with_sharding_constraint mapping logical dims onto mesh axes.

    A logical axis whose dimension does not divide its mesh axes is dropped
    (replicated) — e.g. batch=1 long-context decode, or gemma's single KV head
    on a 16-way model axis.
    """
    if _RULES is None:
        return x
    spec = []
    for dim_size, logical in zip(x.shape, dims):
        if logical is None:
            spec.append(None)
            continue
        axes, n = _RULES[logical]
        spec.append(axes if dim_size % n == 0 else None)
    return jax.lax.with_sharding_constraint(x, P(*spec))


def constrain_first_fit(x: jax.Array, candidates) -> jax.Array:
    """Apply the first candidate whose every named axis divides its dim.

    Used for attention activations: prefer head-sharding (TP); else spread the
    batch over dp x model (pure-DP attention); else query-sequence (context)
    parallelism — covers head counts that do not divide the model axis
    (e.g. qwen2.5's 40 heads on a 16-way axis).
    """
    if _RULES is None:
        return x
    for dims in candidates:
        ok = True
        for dim_size, logical in zip(x.shape, dims):
            if logical is not None and dim_size % _RULES[logical][1] != 0:
                ok = False
                break
        if ok:
            return constrain(x, dims)
    return x


# ---------------------------------------------------------------------------
# Parameter rules (FSDP x TP; see DESIGN.md §5)
# ---------------------------------------------------------------------------

# leaf names whose (d_in, d_out) orientation is output-projection-like
_OUT_PROJ = {"wo", "wo_mlp", "w_out", "wv_c"}
# leaf names replicated outright (norm scales / tiny vectors / adapters)
_REPLICATED = {"scale", "bias", "kv_norm_scale", "gate_norm_scale", "ln_scale",
               "w0", "mix_r", "mix_k", "mix_v", "mix_w", "mix_g",
               "a_log", "d_skip", "dt_bias", "bonus_u",
               "attn_a", "attn_b", "mlp_a", "mlp_b",
               "decay_a", "decay_b"}
_BIAS_MODEL = {"bq", "bk", "bv", "conv_x_b", "conv_bc_b"}
_CONV_MODEL = {"conv_x_w", "conv_bc_w"}


def param_partition_spec(path: str, shape: tuple[int, ...], rules: dict) -> P:
    """PartitionSpec for one parameter (or mirrored optimizer-state) leaf."""
    dp, dp_n = rules["batch"]
    model, model_n = rules["model"]
    name = path.split("/")[-1]

    def fit(axes, n, dim):
        return axes if dim % n == 0 else None

    if name in _REPLICATED or len(shape) == 0:
        return P()
    if name == "embed":
        v, d = shape[-2], shape[-1]
        lead = (None,) * (len(shape) - 2)
        return P(*lead, fit(model, model_n, v), fit(dp, dp_n, d))
    if name in _BIAS_MODEL or name in _CONV_MODEL:
        lead = (None,) * (len(shape) - 1)
        return P(*lead, fit(model, model_n, shape[-1]))
    if name in ("we_in", "we_gate", "we_out"):
        lead = (None,) * (len(shape) - 3)
        e, di, do = shape[-3], shape[-2], shape[-1]
        # NOTE: pod-spanning EP (experts over pod x model) was measured and
        # REFUTED — cross-pod expert all-to-alls cost more than per-pod
        # expert replication saves (deepseek 2x16x16: 27 -> 40 GB temp,
        # 11 -> 31 GB collectives). Experts stay intra-pod.
        if e % model_n == 0:
            return P(*lead, model, fit(dp, dp_n, di), None)   # EP + FSDP
        if name == "we_out":  # TP over the contraction (f) dim
            return P(*lead, None, fit(model, model_n, di), fit(dp, dp_n, do))
        return P(*lead, None, fit(dp, dp_n, di), fit(model, model_n, do))
    if name == "router":
        lead = (None,) * (len(shape) - 2)
        return P(*lead, fit(dp, dp_n, shape[-2]), None)
    if len(shape) >= 2:
        di, do = shape[-2], shape[-1]
        lead = (None,) * (len(shape) - 2)
        if name in _OUT_PROJ:
            return P(*lead, fit(model, model_n, di), fit(dp, dp_n, do))
        return P(*lead, fit(dp, dp_n, di), fit(model, model_n, do))
    return P(*((None,) * (len(shape) - 1)), fit(model, model_n, shape[-1]))


def constrain_param_tree(tree: Pytree) -> Pytree:
    """Re-pin per-layer parameter slices to their FSDP x TP spec inside scan
    bodies (keeps weight all-gathers per-layer; see module docstring)."""
    if _RULES is None:
        return tree

    def f(path, leaf):
        entries = []
        for k in path:
            if hasattr(k, "key"):
                entries.append(str(k.key))
            elif hasattr(k, "name"):
                entries.append(str(k.name))
            else:
                entries.append(str(getattr(k, "idx", k)))
        spec = param_partition_spec("/".join(entries), leaf.shape, _RULES)
        return jax.lax.with_sharding_constraint(leaf, spec)

    return jax.tree_util.tree_map_with_path(f, tree)


def stream_cast(tree: Pytree, cfg) -> Pytree:
    """Cast >=2-D fp32 weights to the compute dtype BEFORE sharded use.

    The cast is elementwise (shard-local), so every downstream FSDP
    all-gather and gradient reduction moves bf16 instead of fp32 — half the
    wire bytes. 1-D leaves (norm scales, biases) stay fp32 for accuracy.
    """
    import jax.numpy as jnp

    if not getattr(cfg, "weight_stream_bf16", False):
        return tree
    dt = jnp.dtype(cfg.compute_dtype)

    def f(x):
        if x.ndim >= 2 and x.dtype == jnp.float32:
            return x.astype(dt)
        return x

    return jax.tree.map(f, tree)
