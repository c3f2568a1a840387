"""Flagship model: a sharded decoder-only transformer LM (pure pytree).

The checkpointing framework itself carries no model (the reference,
torchsnapshot, is model-free — SURVEY.md §0); this module provides the
*workload* that exercises it: realistic multi-axis-sharded training state
(params + optax optimizer state + step counter + PRNG key) over a
``jax.sharding.Mesh``, which is exactly the state layout the sharded-array
preparers (sharded_io_preparer.py) must persist and elastically restore.

Parallelism layout (GSPMD — shardings annotated, XLA inserts collectives):

- mesh axes ``('dp', 'sp', 'tp')``:
  - **dp**  — data parallel over batch; also ZeRO/FSDP-style parameter
    sharding: every 2-d weight shards its non-tp dim over ``dp``.
  - **tp**  — Megatron-style tensor parallel: qkv / mlp-in are
    column-parallel (output features over ``tp``), out-proj / mlp-out are
    row-parallel (input features over ``tp``).
  - **sp**  — sequence/context parallel: activations between blocks are
    constrained to ``P('dp', 'sp', None)`` (sequence dim sharded); inside
    attention the constraint flips to heads-sharded
    ``P('dp', None, 'tp', None)``, so XLA inserts the sp↔tp all-to-alls
    (Ulysses-style sequence parallelism).
  - **ep**  — expert parallel for MoE blocks: expert-stacked weights shard
    their expert dim over the ``sp`` axis (the standard ep=sp axis-sharing:
    both exist to scale the same per-token dimension).

Pipeline parallelism is intentionally not modeled via GSPMD annotations
(it is a schedule, not a sharding); see parallel/pipeline.py.
"""

from __future__ import annotations

import dataclasses
import functools
import math
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
import optax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..ops.attention import causal_attention
from ..ops.flash_attention import flash_causal_attention
from ..ops.ring_attention import ring_causal_attention


def _pallas_interpret() -> bool:
    """Pallas kernels compile natively only on TPU; everywhere else (CPU
    meshes in tests, the virtual-device dryrun) they run interpreted."""
    return jax.devices()[0].platform != "tpu"

Params = Dict[str, Any]


@dataclasses.dataclass(frozen=True)
class TransformerConfig:
    vocab_size: int = 32768
    d_model: int = 512
    n_heads: int = 8
    n_layers: int = 4
    d_ff: int = 2048
    n_experts: int = 0  # 0 = dense MLP in every block
    moe_every: int = 2  # every k-th block is MoE (when n_experts > 0)
    dtype: Any = jnp.bfloat16
    learning_rate: float = 1e-3
    # "ulysses": heads-sharded attention, sp↔tp all-to-alls at the block
    # boundary (short/medium context). "flash": same layout, but the dense
    # einsum is replaced by the Pallas flash kernel
    # (ops/flash_attention.py — O(block·d) VMEM instead of s² HBM logits;
    # requires seq % 128 == 0 on TPU). "ring": sequence stays sharded and
    # KV blocks rotate the sp ring (ops/ring_attention.py — long context,
    # O(seq_local^2) memory per device). "ring_flash": ring whose
    # per-step blockwise attention runs in the flash kernel (long context
    # without the O(seq_local^2) HBM intermediate either).
    attn_impl: str = "ulysses"
    # > 0: a fine-tune over a frozen base (LoRA, arXiv:2106.09685). Every
    # block gains a rank-r adapter pair on the fused qkv projection
    # (scale alpha / r = 1), and the adapters are all that trains: every
    # other leaf leaves a step as the bits it entered with, and the
    # optimizer state holds moments for the adapters alone.
    lora_rank: int = 0

    @property
    def head_dim(self) -> int:
        return self.d_model // self.n_heads


def make_mesh(
    n_devices: Optional[int] = None, devices: Optional[list] = None
) -> Mesh:
    """Build a ``('dp', 'sp', 'tp')`` mesh over ``n_devices``.

    Factors are assigned tp-first (tensor parallel wants the fastest ICI
    hops), then sp, then dp — e.g. 8 devices → (dp=2, sp=2, tp=2),
    4 → (1, 2, 2), 2 → (1, 1, 2), 1 → (1, 1, 1).
    """
    if devices is None:
        devices = jax.devices()
        if n_devices is not None:
            devices = devices[:n_devices]
    n = len(devices)
    tp = 2 if n % 2 == 0 else 1
    rem = n // tp
    sp = 2 if rem % 2 == 0 else 1
    dp = rem // sp
    arr = np.asarray(devices).reshape(dp, sp, tp)
    return Mesh(arr, axis_names=("dp", "sp", "tp"))


def _is_moe_layer(cfg: TransformerConfig, i: int) -> bool:
    return cfg.n_experts > 0 and (i % cfg.moe_every == cfg.moe_every - 1)


def param_shardings(cfg: TransformerConfig, mesh: Mesh) -> Params:
    """NamedSharding pytree matching :func:`init_params` structure."""

    def ns(*spec: Any) -> NamedSharding:
        return NamedSharding(mesh, P(*spec))

    layers = []
    for i in range(cfg.n_layers):
        block = {
            "ln1_scale": ns(None),
            "ln2_scale": ns(None),
            # column-parallel fused qkv: (d_model, 3 * d_model)
            "wqkv": ns("dp", "tp"),
            # row-parallel out proj: (d_model, d_model)
            "wo": ns("tp", "dp"),
        }
        if _is_moe_layer(cfg, i):
            block["router"] = ns(None, None)  # (d_model, n_experts)
            block["w_in"] = ns("sp", "dp", "tp")  # (E, d_model, d_ff)
            block["w_out"] = ns("sp", "tp", "dp")  # (E, d_ff, d_model)
        else:
            block["w_in"] = ns("dp", "tp")  # (d_model, d_ff)
            block["w_out"] = ns("tp", "dp")  # (d_ff, d_model)
        if cfg.lora_rank:
            block["lora_a"] = ns("dp", None)  # (d_model, r)
            block["lora_b"] = ns(None, "tp")  # (r, 3 * d_model)
        layers.append(block)
    return {
        # d_model over tp: the token gather is then local on every device
        # (vocab-dim sharding would force a masked-gather + collective).
        "embed": ns(None, "tp"),  # (vocab, d_model)
        "layers": layers,
        "ln_f_scale": ns(None),
        "unembed": ns("dp", "tp"),  # (d_model, vocab)
    }


def init_params(
    cfg: TransformerConfig,
    rng: jax.Array,
    mesh: Optional[Mesh] = None,
) -> Params:
    """Initialize parameters; sharded onto ``mesh`` when given.

    Init math runs inside ``jax.jit`` with ``out_shardings`` so each device
    materializes only its own shard (no full-model host copy — matters for
    the 20 GB-class benchmark configs).
    """

    def _init(rng: jax.Array) -> Params:
        n_keys = 3 + 5 * cfg.n_layers
        keys = iter(jax.random.split(rng, n_keys))
        # The adapters draw from a stream of their own: the base keeps
        # the key schedule, and so the weights, it has without them.
        if cfg.lora_rank:
            lora_keys = jax.random.split(jax.random.fold_in(rng, 1), cfg.n_layers)

        def dense(key: jax.Array, shape: Tuple[int, ...]) -> jax.Array:
            fan_in = shape[-2] if len(shape) >= 2 else shape[-1]
            std = 1.0 / math.sqrt(fan_in)
            return (jax.random.normal(key, shape, dtype=jnp.float32) * std).astype(
                cfg.dtype
            )

        layers = []
        for i in range(cfg.n_layers):
            block = {
                "ln1_scale": jnp.ones((cfg.d_model,), dtype=cfg.dtype),
                "ln2_scale": jnp.ones((cfg.d_model,), dtype=cfg.dtype),
                "wqkv": dense(next(keys), (cfg.d_model, 3 * cfg.d_model)),
                "wo": dense(next(keys), (cfg.d_model, cfg.d_model)),
            }
            if _is_moe_layer(cfg, i):
                block["router"] = dense(next(keys), (cfg.d_model, cfg.n_experts))
                block["w_in"] = dense(
                    next(keys), (cfg.n_experts, cfg.d_model, cfg.d_ff)
                )
                block["w_out"] = dense(
                    next(keys), (cfg.n_experts, cfg.d_ff, cfg.d_model)
                )
            else:
                next(keys)  # keep key schedule layer-count-stable
                block["w_in"] = dense(next(keys), (cfg.d_model, cfg.d_ff))
                block["w_out"] = dense(next(keys), (cfg.d_ff, cfg.d_model))
            if cfg.lora_rank:
                block["lora_a"] = dense(lora_keys[i], (cfg.d_model, cfg.lora_rank))
                block["lora_b"] = jnp.zeros(
                    (cfg.lora_rank, 3 * cfg.d_model), dtype=cfg.dtype
                )
            layers.append(block)
        return {
            "embed": dense(next(keys), (cfg.vocab_size, cfg.d_model)),
            "layers": layers,
            "ln_f_scale": jnp.ones((cfg.d_model,), dtype=cfg.dtype),
            "unembed": dense(next(keys), (cfg.d_model, cfg.vocab_size)),
        }

    if mesh is None:
        return jax.jit(_init)(rng)
    shardings = param_shardings(cfg, mesh)
    return jax.jit(_init, out_shardings=shardings)(rng)


def _rmsnorm(x: jax.Array, scale: jax.Array) -> jax.Array:
    var = jnp.mean(jnp.square(x.astype(jnp.float32)), axis=-1, keepdims=True)
    return (x * jax.lax.rsqrt(var + 1e-6).astype(x.dtype)) * scale


def _constrain(x: jax.Array, mesh: Optional[Mesh], *spec: Any) -> jax.Array:
    if mesh is None:
        return x
    return jax.lax.with_sharding_constraint(x, NamedSharding(mesh, P(*spec)))


def _moe_mlp(block: Params, x: jax.Array) -> jax.Array:
    """Soft-routed MoE: every expert computed, outputs gate-combined.

    Shape-static (no dynamic dispatch), so it jits cleanly and the expert
    einsums shard over the ``sp`` (=ep) axis via the stacked-weight
    shardings. Token-dropping top-k dispatch with all_to_all is a later
    optimization; for checkpointing purposes the state layout is identical.
    """
    gates = jax.nn.softmax(
        jnp.einsum("bsd,de->bse", x, block["router"].astype(jnp.float32)), axis=-1
    ).astype(x.dtype)
    h = jnp.einsum("bsd,edf->ebsf", x, block["w_in"])
    h = jax.nn.gelu(h)
    y = jnp.einsum("ebsf,efd->ebsd", h, block["w_out"])
    return jnp.einsum("ebsd,bse->bsd", y, gates)


def _flash_attention_sharded(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    mesh: Optional[Mesh],
    interpret: bool,
) -> jax.Array:
    """Flash attention under GSPMD: a ``pallas_call`` is a custom call XLA
    cannot partition, so on a mesh it must be wrapped in ``shard_map`` over
    the batch/head axes (sequence replicated — the Ulysses layout) to run
    per-device; single-device calls go straight through."""
    if mesh is None:
        return flash_causal_attention(q, k, v, interpret=interpret)
    has_dp = "dp" in mesh.axis_names
    has_tp = "tp" in mesh.axis_names
    spec = P("dp" if has_dp else None, None, "tp" if has_tp else None, None)
    fn = jax.shard_map(
        functools.partial(flash_causal_attention, interpret=interpret),
        mesh=mesh,
        in_specs=(spec, spec, spec),
        out_specs=spec,
        check_vma=False,
    )
    return fn(q, k, v)


def forward(
    cfg: TransformerConfig,
    params: Params,
    tokens: jax.Array,
    mesh: Optional[Mesh] = None,
) -> jax.Array:
    """Token ids ``(batch, seq)`` → logits ``(batch, seq, vocab)``.

    Between blocks activations are sequence-sharded (sp); inside attention
    they are heads-sharded (tp). With ``mesh=None`` the same trace runs
    single-device (the graft ``entry()`` path).
    """
    if cfg.attn_impl not in ("ulysses", "flash", "ring", "ring_flash"):
        # A typo must not silently run the dense path the user was
        # explicitly opting out of.
        raise ValueError(
            f"unknown attn_impl {cfg.attn_impl!r}; expected one of "
            f"'ulysses', 'flash', 'ring', 'ring_flash'"
        )
    x = jnp.take(params["embed"], tokens, axis=0)
    x = _constrain(x, mesh, "dp", "sp", None)
    b, s, d = x.shape
    for i, block in enumerate(params["layers"]):
        h = _rmsnorm(x, block["ln1_scale"])
        qkv = jnp.einsum("bsd,dz->bsz", h, block["wqkv"])
        if "lora_a" in block:
            qkv = qkv + jnp.einsum(
                "bsr,rz->bsz",
                jnp.einsum("bsd,dr->bsr", h, block["lora_a"]),
                block["lora_b"],
            )
        qkv = qkv.reshape(b, s, 3, cfg.n_heads, cfg.head_dim)
        if cfg.attn_impl in ("ring", "ring_flash") and mesh is not None:
            # Sequence stays sp-sharded; KV blocks rotate the ring.
            qkv = _constrain(qkv, mesh, "dp", "sp", None, "tp", None)
            q, k, v = qkv[:, :, 0], qkv[:, :, 1], qkv[:, :, 2]
            attn = ring_causal_attention(
                q,
                k,
                v,
                mesh=mesh,
                use_flash=(cfg.attn_impl == "ring_flash"),
                interpret=_pallas_interpret(),
            )
        else:
            # Ulysses: resharding to heads-over-tp makes XLA insert the
            # sp↔tp all-to-alls around the attention op.
            qkv = _constrain(qkv, mesh, "dp", None, None, "tp", None)
            q, k, v = qkv[:, :, 0], qkv[:, :, 1], qkv[:, :, 2]
            if cfg.attn_impl == "flash":
                if s % 128:
                    # Never degrade silently: the user chose flash to avoid
                    # the s² logits tensor; a quiet dense fallback would
                    # reintroduce exactly that (OOM at long seq).
                    raise ValueError(
                        f"attn_impl='flash' requires seq % 128 == 0, got "
                        f"seq={s}; pad the sequence or use attn_impl="
                        f"'ulysses'"
                    )
                attn = _flash_attention_sharded(
                    q, k, v, mesh, interpret=_pallas_interpret()
                )
            else:
                attn = causal_attention(q, k, v)
        attn = attn.reshape(b, s, d)
        x = x + _constrain(
            jnp.einsum("bsz,zd->bsd", attn, block["wo"]), mesh, "dp", "sp", None
        )
        h = _rmsnorm(x, block["ln2_scale"])
        if "router" in block:
            y = _moe_mlp(block, h)
        else:
            y = jnp.einsum(
                "bsf,fd->bsd", jax.nn.gelu(jnp.einsum("bsd,df->bsf", h, block["w_in"])),
                block["w_out"],
            )
        x = x + _constrain(y, mesh, "dp", "sp", None)
    x = _rmsnorm(x, params["ln_f_scale"])
    return jnp.einsum("bsd,dv->bsv", x, params["unembed"]).astype(jnp.float32)


# ----------------------------------------------------------------------
# Training state + step
# ----------------------------------------------------------------------


@dataclasses.dataclass
class TrainState:
    """The checkpointable unit: what Snapshot.take persists for this model."""

    params: Params
    opt_state: Any
    step: jax.Array  # scalar int32
    rng: jax.Array  # PRNGKey

    def as_pytree(self) -> Dict[str, Any]:
        return {
            "params": self.params,
            "opt_state": self.opt_state,
            "step": self.step,
            "rng": self.rng,
        }


jax.tree_util.register_dataclass(
    TrainState, ["params", "opt_state", "step", "rng"], []
)


def _optimizer(cfg: TransformerConfig) -> optax.GradientTransformation:
    return optax.adamw(cfg.learning_rate)


_ADAPTER_LEAVES = ("lora_a", "lora_b")


def _trainable(cfg: TransformerConfig, params: Params) -> Params:
    """The leaves the optimizer sees, of ``params`` or of any tree laid
    out like it (its shardings): all of it, or with ``lora_rank`` the
    adapters alone."""
    if not cfg.lora_rank:
        return params
    return {
        "layers": [
            {k: block[k] for k in _ADAPTER_LEAVES} for block in params["layers"]
        ]
    }


def _with_trainable(
    cfg: TransformerConfig, params: Params, trainable: Params
) -> Params:
    """``params`` with its trainable leaves replaced. A frozen leaf is
    the object that came in: through a donated step it goes untouched,
    never as ``p + 0`` (which is not ``p`` for a negative zero)."""
    if not cfg.lora_rank:
        return trainable
    layers = [
        {**block, **adapters}
        for block, adapters in zip(params["layers"], trainable["layers"])
    ]
    return {**params, "layers": layers}


def init_train_state(
    cfg: TransformerConfig,
    seed: int = 0,
    mesh: Optional[Mesh] = None,
) -> TrainState:
    rng = jax.random.PRNGKey(seed)
    params = init_params(cfg, rng, mesh=mesh)
    opt = _optimizer(cfg)
    step = jnp.zeros((), dtype=jnp.int32)
    trainable = _trainable(cfg, params)
    if mesh is None:
        opt_state = jax.jit(opt.init)(trainable)
    else:
        # Adam moments are zeros_like(params): no data flows from the
        # params, so sharding propagation has nothing to follow and a bare
        # jit leaves both moments (2/3 of the state) whole on device 0
        # until the first step reshards them. Shard them like the params
        # and replicate the scalars, so the state enters the step with the
        # shardings it leaves with (one compile, and restore destinations
        # laid out as trained).
        replicated = NamedSharding(mesh, P())
        opt_shardings = optax.tree_map_params(
            opt,
            lambda _, sharding: sharding,
            jax.eval_shape(opt.init, trainable),
            _trainable(cfg, param_shardings(cfg, mesh)),
            transform_non_params=lambda _: replicated,
        )
        opt_state = jax.jit(opt.init, out_shardings=opt_shardings)(trainable)
        step, rng = jax.device_put((step, rng), replicated)
    return TrainState(params=params, opt_state=opt_state, step=step, rng=rng)


def state_shardings(state: TrainState) -> Dict[str, Any]:
    """Sharding pytree of a live train state (restore destinations)."""
    return jax.tree_util.tree_map(
        lambda leaf: leaf.sharding, state.as_pytree()
    )


def make_train_step(
    cfg: TransformerConfig,
    mesh: Optional[Mesh] = None,
) -> Callable[[TrainState, jax.Array], Tuple[TrainState, jax.Array]]:
    """Build the jitted full training step (fwd + loss + bwd + adamw).
    With ``cfg.lora_rank`` the loss is differentiated, and adamw run,
    over the adapters alone."""
    opt = _optimizer(cfg)

    def loss_fn(params: Params, tokens: jax.Array) -> jax.Array:
        logits = forward(cfg, params, tokens, mesh=mesh)
        targets = tokens[:, 1:]
        logits = logits[:, :-1]
        losses = optax.softmax_cross_entropy_with_integer_labels(logits, targets)
        return jnp.mean(losses)

    def train_step(
        state: TrainState, tokens: jax.Array
    ) -> Tuple[TrainState, jax.Array]:
        if mesh is not None:
            tokens = jax.lax.with_sharding_constraint(
                tokens, NamedSharding(mesh, P("dp", None))
            )
        trainable = _trainable(cfg, state.params)
        loss, grads = jax.value_and_grad(
            lambda t: loss_fn(_with_trainable(cfg, state.params, t), tokens)
        )(trainable)
        updates, new_opt_state = opt.update(grads, state.opt_state, trainable)
        new_params = _with_trainable(
            cfg, state.params, optax.apply_updates(trainable, updates)
        )
        new_rng = jax.random.fold_in(state.rng, state.step)
        return (
            TrainState(
                params=new_params,
                opt_state=new_opt_state,
                step=state.step + 1,
                rng=new_rng,
            ),
            loss,
        )

    return jax.jit(train_step, donate_argnums=(0,))
