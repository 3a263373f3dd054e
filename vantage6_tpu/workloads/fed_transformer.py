"""Federated causal-LM training: stations × sequence-parallel transformer.

The long-context flagship: cross-silo federated training of a decoder-only
transformer where each station's sequences are sharded over its sub-mesh
(`device` axis) and attention runs as ring attention over ICI
(vantage6_tpu.parallel) — context length scales with devices-per-station
while the station axis keeps the federation's data-parallel isolation:
per-station gradients psum only over `device`, never across stations;
cross-station aggregation is an explicit FedAvg (fed.collectives.fed_mean).

No reference counterpart (SURVEY.md §5: sequence models absent upstream) —
this is a capability the TPU rebuild adds, built from the same station
primitives as the tabular workloads.
"""
from __future__ import annotations

import dataclasses
from functools import partial
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np
import optax
from jax import lax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from vantage6_tpu.core.mesh import STATION_AXIS, _largest_divisor_leq
from vantage6_tpu.fed import collectives
from vantage6_tpu.ops.flash_attention import (
    flash_attention,
    recompute_attention,
)
from vantage6_tpu.parallel.ring_attention import ring_attention
from vantage6_tpu.runtime.profiling import device_launch, engine_call

SEQ_AXIS = "device"  # sequence parallelism rides the within-station axis


@dataclasses.dataclass(frozen=True)
class TransformerConfig:
    vocab: int = 256
    d_model: int = 64
    n_heads: int = 4
    n_layers: int = 2
    max_len: int = 2048
    # Mixed precision: params/optimizer stay float32 (master weights); all
    # matmuls run in `dtype`. bfloat16 is the MXU-rate dtype on TPU; softmax
    # statistics, layernorm and the loss stay f32 either way.
    dtype: Any = jnp.float32
    # "ring": exact ring attention over the sequence axis (any seq_devices).
    # "flash": the Pallas flash kernel (ops.flash_attention) — requires the
    # full sequence on each device (seq_devices == 1, enforced by
    # make_engine); `flash_interpret` runs it in interpret mode on CPU.
    # "recompute": flash-memory attention WITHOUT pallas (blockwise jnp
    # forward + recompute backward; ops.recompute_attention) — same
    # seq_devices == 1 constraint. "flash" runs the kernel or raises; it
    # never gives way to "recompute".
    attention: str = "ring"
    flash_interpret: bool = False
    # Rematerialization: drop every layer's activations on the forward pass
    # and recompute them during backward (jax.checkpoint per layer block).
    # Activation memory falls from O(n_layers * B * T * d) to O(B * T * d)
    # — the standard long-context trade (FLOPs ~+33% for the extra
    # forward) — and composes with the attention choices above (recompute
    # attention already avoids the [T, T] residuals WITHIN a layer; remat
    # drops the per-layer residual stream BETWEEN layers). Exact in math;
    # numerically identical to f32 rounding (XLA may fuse differently
    # across the checkpoint boundary — measured ~1 ULP on the loss).
    remat: bool = False

    @property
    def head_dim(self) -> int:
        assert self.d_model % self.n_heads == 0
        return self.d_model // self.n_heads


def init_params(key: jax.Array, cfg: TransformerConfig) -> dict[str, Any]:
    keys = jax.random.split(key, 2 + 4 * cfg.n_layers)
    s = 0.02
    params: dict[str, Any] = {
        "embed": s * jax.random.normal(keys[0], (cfg.vocab, cfg.d_model)),
        "pos": s * jax.random.normal(keys[1], (cfg.max_len, cfg.d_model)),
        "layers": [],
    }
    for i in range(cfg.n_layers):
        k = keys[2 + 4 * i : 6 + 4 * i]
        params["layers"].append(
            {
                "qkv": s * jax.random.normal(k[0], (cfg.d_model, 3 * cfg.d_model)),
                "proj": s * jax.random.normal(k[1], (cfg.d_model, cfg.d_model)),
                "w_up": s * jax.random.normal(k[2], (cfg.d_model, 4 * cfg.d_model)),
                "w_down": s * jax.random.normal(k[3], (4 * cfg.d_model, cfg.d_model)),
            }
        )
    return params


def _ln(x: jax.Array) -> jax.Array:
    # normalization statistics in f32 even under bf16 compute
    xf = x.astype(jnp.float32)
    mu = jnp.mean(xf, -1, keepdims=True)
    var = jnp.var(xf, -1, keepdims=True)
    return ((xf - mu) * lax.rsqrt(var + 1e-6)).astype(x.dtype)


def forward_local(
    params: dict[str, Any],
    tokens_local: jax.Array,  # [B, T_local] — this device's sequence shard
    cfg: TransformerConfig,
    axis_name: str = SEQ_AXIS,
) -> jax.Array:
    """Logits [B, T_local, V] for this shard; attention spans the FULL
    sequence via the ring."""
    b, t_local = tokens_local.shape
    offset = lax.axis_index(axis_name) * t_local  # global positions

    def cast(w: jax.Array) -> jax.Array:
        return w.astype(cfg.dtype)

    with jax.named_scope("embed"):
        x = cast(params["embed"])[tokens_local]
        x = x + cast(
            lax.dynamic_slice_in_dim(params["pos"], offset, t_local, 0)
        )[None]

    def layer_block(x, layer):
        layer = jax.tree.map(cast, layer)
        h = _ln(x)
        qkv = h @ layer["qkv"]
        q, k, v = jnp.split(qkv, 3, axis=-1)
        q = q.reshape(b, t_local, cfg.n_heads, cfg.head_dim)
        k = k.reshape(b, t_local, cfg.n_heads, cfg.head_dim)
        v = v.reshape(b, t_local, cfg.n_heads, cfg.head_dim)
        if cfg.attention in ("flash", "recompute"):
            # both want head-major [B, H, T, D]; offsets keep the causal
            # mask correct for any sequence shard (here the full sequence —
            # make_engine enforces seq_devices == 1 for these modes)
            impl = (
                flash_attention if cfg.attention == "flash"
                else recompute_attention
            )
            kw = (
                {"interpret": cfg.flash_interpret}
                if cfg.attention == "flash" else {}
            )
            with jax.named_scope("attention"):
                attn = impl(
                    q.transpose(0, 2, 1, 3),
                    k.transpose(0, 2, 1, 3),
                    v.transpose(0, 2, 1, 3),
                    q_offset=offset,
                    k_offset=offset,
                    causal=True,
                    **kw,
                ).transpose(0, 2, 1, 3)
        else:
            with jax.named_scope("attention"):
                attn = ring_attention(q, k, v, axis_name, causal=True)
        x = x + attn.reshape(b, t_local, cfg.d_model) @ layer["proj"]
        with jax.named_scope("mlp"):
            h = _ln(x)
            return x + jax.nn.gelu(h @ layer["w_up"]) @ layer["w_down"]

    if cfg.remat:
        layer_block = jax.checkpoint(layer_block)
    for layer in params["layers"]:
        x = layer_block(x, layer)
    with jax.named_scope("lm_head_loss"):
        return _ln(x) @ cast(params["embed"]).T


def loss_local(
    params: dict[str, Any],
    tokens_local: jax.Array,
    cfg: TransformerConfig,
    axis_name: str = SEQ_AXIS,
) -> jax.Array:
    """Mean next-token CE over the GLOBAL sequence (psum over shards).

    Within a shard, position t predicts t+1; each shard's final token has
    its target on the next shard, so that position is masked out (T/P - 1
    predictions per shard — negligible at scale, exact bookkeeping here).
    """
    logits = forward_local(params, tokens_local, cfg, axis_name)
    with jax.named_scope("lm_head_loss"):
        targets = tokens_local[:, 1:]
        logp = jax.nn.log_softmax(logits[:, :-1].astype(jnp.float32))
        nll = -jnp.take_along_axis(logp, targets[..., None], axis=-1)[..., 0]
        local_sum = jnp.sum(nll)
        local_cnt = jnp.asarray(nll.size, jnp.float32)
    total = lax.psum(local_sum, axis_name)
    count = lax.psum(local_cnt, axis_name)
    return total / count


@dataclasses.dataclass(eq=False)  # identity hash: engine is a jit static arg
class FedTransformer:
    """Training engine over a ('station', 'device') mesh."""

    mesh: Mesh
    cfg: TransformerConfig
    optimizer: Any

    def init(self, key: jax.Array) -> tuple[Any, Any]:
        # params AND the whole optimizer state are committed to the mesh:
        # optax's step count is born unplaced, and a round returns it
        # placed — left so, the second round() would be a second compile
        rep = NamedSharding(self.mesh, P())
        params = jax.device_put(init_params(key, self.cfg), rep)
        return params, jax.device_put(self.optimizer.init(params), rep)

    def shard_tokens(self, tokens: np.ndarray | jax.Array) -> jax.Array:
        """[S, B, T] -> sharded (station, none, device)."""
        t = tokens.shape[-1]
        if t > self.cfg.max_len:
            # dynamic_slice would silently CLAMP out-of-range offsets and
            # train with duplicated positional rows — fail loudly instead
            raise ValueError(
                f"sequence length {t} exceeds cfg.max_len={self.cfg.max_len}"
            )
        sh = NamedSharding(self.mesh, P(STATION_AXIS, None, SEQ_AXIS))
        return jax.device_put(jnp.asarray(tokens), sh)

    def round(
        self,
        params: Any,
        opt_state: Any,
        tokens: jax.Array,  # [S, B, T] sharded (station, None, device)
        mask: jax.Array,  # [S] participation
    ) -> tuple[Any, Any, jax.Array]:
        """One federated round: per-station grads (sp inside), FedAvg, step.

        Recorded as an ``engine.call`` span over the whole host side of the
        call and, under it, a ``device.launch`` span around the call into
        the jitted program and nothing else (``n_buffers`` = the array
        leaves handed over)."""
        args = (params, opt_state, tokens, mask)
        with engine_call("fed_transformer.round", 1):
            n_buffers = len(jax.tree.leaves(args))
            with device_launch("fed_transformer.round", n_buffers):
                return self._round(*args)

    @partial(jax.jit, static_argnums=0)
    def _round(
        self, params: Any, opt_state: Any, tokens: jax.Array,
        mask: jax.Array,
    ) -> tuple[Any, Any, jax.Array]:
        def station_body(params, tokens_block):
            # tokens_block: [S/D_s, B, T/P] — the inner vmap walks the
            # stations PACKED into this mesh slot (stations_per_slot > 1
            # when the mesh folds more stations than device slots, same
            # contract as FederationMesh.fed_map)
            def one_station(tok):
                loss, grads = jax.value_and_grad(loss_local)(
                    params, tok, self.cfg
                )
                # reduce over sequence shards WITHIN the station only
                grads = lax.psum(grads, SEQ_AXIS)
                loss = lax.pmean(loss, SEQ_AXIS)
                return loss, grads

            with jax.named_scope("local_train"):
                return jax.vmap(one_station)(tokens_block)

        # Variance checking OFF, same stance (and reason) as fed_map: the
        # station body is a purely local program whose only cross-device
        # reductions are the EXPLICIT psums over SEQ_AXIS above; it also
        # works around the pallas-interpret + VMA interaction that rejects
        # the flash kernel inside a checked shard_map (jax 0.9 asks for
        # exactly this workaround).
        losses, grads = jax.shard_map(
            station_body,
            mesh=self.mesh,
            in_specs=(P(), P(STATION_AXIS, None, SEQ_AXIS)),
            out_specs=(P(STATION_AXIS), P(STATION_AXIS)),
            check_vma=False,
        )(params, tokens)
        # explicit cross-station aggregation: the ONLY place station data mixes
        g_mean = collectives.fed_mean(grads, mask=mask)
        with jax.named_scope("server_update"):
            updates, opt_state = self.optimizer.update(
                g_mean, opt_state, params
            )
            params = optax.apply_updates(params, updates)
        loss = collectives.fed_mean(losses, mask=mask)
        return params, opt_state, loss


def make_engine(
    n_stations: int,
    seq_devices: int,
    cfg: TransformerConfig | None = None,
    lr: float = 1e-3,
    devices: Any = None,
) -> FedTransformer:
    cfg = cfg or TransformerConfig()
    if cfg.attention in ("flash", "recompute") and seq_devices != 1:
        raise ValueError(
            f"attention={cfg.attention!r} needs the full sequence per "
            f"device (seq_devices == 1, got {seq_devices}); use 'ring' for "
            "sequence-parallel runs"
        )
    devs = list(devices if devices is not None else jax.devices())
    if len(devs) < seq_devices:
        raise ValueError(
            f"need at least {seq_devices} devices for {seq_devices} "
            f"sequence shards, have {len(devs)}"
        )
    # station-axis size: the largest divisor of S that fits the hardware —
    # remaining stations FOLD into each slot (stations_per_slot, walked by
    # an inner vmap in round()), the same packing as FederationMesh. One
    # chip can therefore run an S-station federated round; with S*seq
    # devices every station owns real hardware.
    usable_slots = len(devs) // seq_devices
    station_slots = _largest_divisor_leq(n_stations, usable_slots)
    arr = np.array(devs[: station_slots * seq_devices]).reshape(
        station_slots, seq_devices
    )
    mesh = Mesh(arr, (STATION_AXIS, SEQ_AXIS))
    return FedTransformer(mesh=mesh, cfg=cfg, optimizer=optax.adam(lr))


def make_federated_tokens(
    n_stations: int, batch: int, seq_len: int, vocab: int, seed: int = 0
) -> np.ndarray:
    """Synthetic per-station corpora with station-distinct statistics."""
    rng = np.random.default_rng(seed)
    out = np.empty((n_stations, batch, seq_len), np.int32)
    for s in range(n_stations):
        # each station's corpus favors a distinct token range (non-IID)
        center = (s + 1) * vocab // (n_stations + 1)
        vals = rng.normal(center, vocab / 6, (batch, seq_len))
        out[s] = np.clip(np.round(vals), 0, vocab - 1)
    return out
