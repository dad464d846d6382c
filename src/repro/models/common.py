"""Common model machinery: param descriptors, init, norms, RoPE, sharding.

Parameters are declared as trees of :class:`PD` (param descriptors) carrying
shape, *logical axis names*, and init scale.  A single descriptor tree yields
both the materialized param pytree (``init_tree``) and the PartitionSpec
pytree (``spec_tree``) so the two can never drift structurally.
"""
from __future__ import annotations

import dataclasses
import functools
from dataclasses import dataclass
from typing import Any, Callable, Dict, Optional, Sequence, Tuple, Union

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

# ---------------------------------------------------------------------------
# Logical axes.  Physical mapping is decided by AxisRules (launch/shardings).
# ---------------------------------------------------------------------------
# "vocab"    -> model-parallel vocab shard
# "heads"    -> model-parallel attention heads (q)
# "kv"       -> kv heads
# "mlp"      -> model-parallel FFN hidden
# "expert"   -> expert-parallel axis
# "embed"    -> d_model (replicated in megatron-style TP)
# "layers"   -> stacked layer axis for lax.scan (never sharded)
# None       -> replicated


@dataclass(frozen=True)
class PD:
    """Param descriptor: shape + logical axes + init (+ dtype override)."""
    shape: Tuple[int, ...]
    axes: Tuple[Optional[str], ...]
    init: Union[str, float] = "fan_in"   # "fan_in" | "zeros" | "ones" | const std
    dtype: Any = None                    # None -> caller-provided default

    def __post_init__(self):
        assert len(self.shape) == len(self.axes), (self.shape, self.axes)


def is_pd(x: Any) -> bool:
    return isinstance(x, PD)


@functools.partial(jax.jit, static_argnums=(1, 2))
def _init_one(key: jax.Array, pd: PD, dtype) -> jax.Array:
    # jitted so the float32 draw fuses into the cast: a bf16 leaf never
    # holds a float32 temporary of its full size on the device
    dtype = pd.dtype or dtype
    if pd.init == "zeros":
        return jnp.zeros(pd.shape, dtype)
    if pd.init == "ones":
        return jnp.ones(pd.shape, dtype)
    if pd.init == "fan_in":
        # fan-in: product of all but the last dim, stacked layers excluded
        fan_in = 1
        for d, a in zip(pd.shape[:-1], pd.axes[:-1]):
            if a != "layers":
                fan_in *= d
        std = max(fan_in, 1) ** -0.5
    else:
        std = float(pd.init)
    return (jax.random.normal(key, pd.shape, jnp.float32) * std).astype(dtype)


def init_tree(key: jax.Array, tree, dtype=jnp.bfloat16):
    """Materialize a PD tree into a param pytree."""
    leaves, treedef = jax.tree_util.tree_flatten(tree, is_leaf=is_pd)
    keys = jax.random.split(key, max(len(leaves), 1))
    out = [_init_one(k, pd, dtype) for k, pd in zip(keys, leaves)]
    return jax.tree_util.tree_unflatten(treedef, out)


def shape_tree(tree, dtype=jnp.bfloat16):
    """PD tree -> ShapeDtypeStruct tree (no allocation; for dry-runs)."""
    return jax.tree_util.tree_map(
        lambda pd: jax.ShapeDtypeStruct(pd.shape, pd.dtype or dtype),
        tree, is_leaf=is_pd)


def stack_pds(tree, n: int):
    """Add a leading scanned 'layers' axis of length n to every descriptor."""
    def f(pd: PD) -> PD:
        return PD((n,) + pd.shape, ("layers",) + pd.axes, pd.init, pd.dtype)
    return jax.tree_util.tree_map(f, tree, is_leaf=is_pd)


# ---------------------------------------------------------------------------
# Axis rules: logical axis name -> mesh axis (with divisibility fallbacks)
# ---------------------------------------------------------------------------
class AxisRules:
    """Resolves logical param/activation axes to PartitionSpecs for a mesh.

    ``batch_axes`` covers DP ("pod","data"); ``model_axis`` covers TP/EP.
    An axis maps to its mesh axis only when the dimension is divisible by the
    mesh-axis size — otherwise it falls back to replication (documented in
    DESIGN.md, e.g. recurrentgemma's 10 heads on a 16-way model axis).
    """

    def __init__(self, mesh: Optional[jax.sharding.Mesh],
                 options: Optional[Dict[str, Any]] = None):
        self.mesh = mesh
        if mesh is None:
            self.axis_sizes: Dict[str, int] = {}
        else:
            self.axis_sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
        self.batch_axes: Tuple[str, ...] = tuple(
            a for a in ("pod", "data") if a in self.axis_sizes)
        self.model_axis: Optional[str] = "model" if "model" in self.axis_sizes else None
        # execution options threaded to layer implementations (perf levers):
        #   attn_impl: "naive" | "blockwise";  attn_block: int
        #   rwkv_impl: "scan" | "chunked";     rwkv_chunk: int
        self.options: Dict[str, Any] = dict(options or {})
        # what layers report while they are traced, once per compiled
        # program (the MoE layer: its expert GEMMs' rows per token count)
        self.notes: Dict[Any, Any] = {}

    def opt(self, key: str, default: Any = None) -> Any:
        return self.options.get(key, default)

    # -- helpers ----------------------------------------------------------
    def _batch_size_product(self) -> int:
        p = 1
        for a in self.batch_axes:
            p *= self.axis_sizes[a]
        return p

    def batch(self, dim: int):
        """Mesh mapping for a batch dimension of size `dim` (best effort)."""
        axes = list(self.batch_axes)
        while axes:
            prod = 1
            for a in axes:
                prod *= self.axis_sizes[a]
            if dim % prod == 0:
                return tuple(axes) if len(axes) > 1 else axes[0]
            axes.pop(0)  # drop "pod" first, then "data"
        return None

    def model(self, dim: int):
        if self.model_axis and dim % self.axis_sizes[self.model_axis] == 0:
            return self.model_axis
        return None

    def model_size(self) -> int:
        return self.axis_sizes.get("model", 1)

    # -- resolution --------------------------------------------------------
    def resolve(self, axes: Sequence[Optional[str]], shape: Sequence[int]) -> P:
        """Logical axes + concrete dims -> PartitionSpec.

        A mesh axis may appear at most once per spec: the first logical axis
        that claims it wins, later claimants replicate (e.g. MoE expert
        weights (E, d, ff): 'expert' takes "model" so 'mlp' replicates under
        EP; when E is not divisible 'expert' falls back and 'mlp' takes
        "model" — the TP-over-d_ff layout moe_apply uses for mixtral).
        """
        out = []
        used = set()
        for a, d in zip(axes, shape):
            m = None
            if a in ("vocab", "heads", "kv", "mlp", "expert", "kv_seq"):
                m = self.model(d)
            elif a == "batch":
                m = self.batch(d)
            elif a == "zero":  # ZeRO-1 optimizer-state sharding over data
                ds = self.axis_sizes.get("data", 1)
                m = "data" if ds > 1 and d % ds == 0 else None
            elif a in ("embed", "layers", None):
                m = None
            else:
                raise ValueError(f"unknown logical axis {a!r}")
            flat = m if isinstance(m, tuple) else (m,)
            if m is not None and any(f in used for f in flat):
                m = None
            if m is not None:
                used.update(flat)
            out.append(m)
        return P(*out)

    def spec_tree(self, pd_tree):
        return jax.tree_util.tree_map(
            lambda pd: self.resolve(pd.axes, pd.shape), pd_tree, is_leaf=is_pd)

    def constrain(self, x: jax.Array, *axes: Optional[str]) -> jax.Array:
        """with_sharding_constraint by logical axes (no-op without a mesh)."""
        if self.mesh is None or self.mesh.empty:
            return x
        spec = self.resolve(axes, x.shape)
        return jax.lax.with_sharding_constraint(
            x, jax.sharding.NamedSharding(self.mesh, spec))


NO_RULES = AxisRules(None)


# ---------------------------------------------------------------------------
# Numerics
# ---------------------------------------------------------------------------
def rms_norm(x: jax.Array, scale: jax.Array, eps: float = 1e-6,
             zero_centered: bool = False) -> jax.Array:
    dt = x.dtype
    x = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    y = x * jax.lax.rsqrt(var + eps)
    s = (1.0 + scale.astype(jnp.float32)) if zero_centered else scale.astype(jnp.float32)
    return (y * s).astype(dt)


def softcap(x: jax.Array, cap: float) -> jax.Array:
    if not cap:
        return x
    return (cap * jnp.tanh(x.astype(jnp.float32) / cap)).astype(x.dtype)


def rope_freqs(positions: jax.Array, head_dim: int, theta: float) -> Tuple[jax.Array, jax.Array]:
    """positions (...,) -> cos/sin of shape (..., head_dim//2), float32."""
    half = head_dim // 2
    inv = 1.0 / (theta ** (jnp.arange(0, half, dtype=jnp.float32) / half))
    ang = positions.astype(jnp.float32)[..., None] * inv  # (..., half)
    return jnp.cos(ang), jnp.sin(ang)


def apply_rope(x: jax.Array, cos: jax.Array, sin: jax.Array) -> jax.Array:
    """x: (..., n_heads, head_dim); cos/sin: broadcastable (..., 1, head_dim//2)."""
    half = x.shape[-1] // 2
    x1, x2 = x[..., :half], x[..., half:]
    dt = x.dtype
    x1f, x2f = x1.astype(jnp.float32), x2.astype(jnp.float32)
    return jnp.concatenate(
        [x1f * cos - x2f * sin, x2f * cos + x1f * sin], axis=-1).astype(dt)


def activation(name: str) -> Callable[[jax.Array], jax.Array]:
    return {"silu": jax.nn.silu, "gelu": jax.nn.gelu, "relu": jax.nn.relu}[name]


def cross_entropy_loss(logits: jax.Array, labels: jax.Array,
                       mask: Optional[jax.Array] = None) -> jax.Array:
    """Mean token NLL in f32.  logits (..., V), labels (...) int32."""
    logits = logits.astype(jnp.float32)
    lse = jax.nn.logsumexp(logits, axis=-1)
    picked = jnp.take_along_axis(logits, labels[..., None], axis=-1)[..., 0]
    nll = lse - picked
    if mask is not None:
        mask = mask.astype(jnp.float32)
        return jnp.sum(nll * mask) / jnp.maximum(jnp.sum(mask), 1.0)
    return jnp.mean(nll)
