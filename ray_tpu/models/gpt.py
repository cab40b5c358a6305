"""Decoder-only transformer (GPT family) — the flagship model.

Matches the reference's north-star workload (GPT-J-6B fine-tune,
BASELINE.json / ``release/air_examples/gptj_deepspeed_finetuning``) but built
TPU-first:

* parameters are a plain pytree with the layer dimension stacked in front, so
  the depth loop is one ``lax.scan`` (constant compile time in depth) with
  ``jax.checkpoint`` rematerialization per block (HBM ∝ 1 layer of
  activations);
* compute in bfloat16 on the MXU, params kept fp32 (master copy) and cast at
  use; fp32 softmax/layernorm accumulations;
* no data-dependent Python control flow — everything jits once;
* sharding is external: ``ray_tpu.parallel.sharding`` maps parameter paths to
  PartitionSpecs; this file only places activation constraints.
"""

from __future__ import annotations

import dataclasses
from typing import Optional

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name

from ray_tpu.ops.attention import causal_attention


@dataclasses.dataclass(frozen=True)
class GPTConfig:
    vocab_size: int = 50_304          # multiple of 128 for MXU lanes
    seq_len: int = 1024
    d_model: int = 768
    n_layers: int = 12
    n_heads: int = 12
    dtype: str = "bfloat16"           # activation/compute dtype
    remat: bool = True
    #: "full" recomputes the whole block in backward (min HBM);
    #: "dots" saves matmul outputs (recomputes only cheap elementwise —
    #: more HBM, fewer backward FLOPs); "attn" saves only the attention
    #: output (skips recomputing flash attention, the priciest recompute,
    #: at one (b,s,d) tensor per layer); "big" saves attention + MLP
    #: hidden. Tune per chip generation.
    remat_policy: str = "full"
    #: Blockwise fused cross-entropy in gpt_loss: never materializes the
    #: (tokens, vocab) logits (the largest HBM consumer at bench shapes)
    #: and runs the lm-head matmuls in the activation dtype on the MXU.
    fused_loss: bool = True
    #: Vocab chunk count for the fused loss (None = memory-conservative
    #: auto). 1 = one full-width pass: fastest when HBM headroom allows the
    #: (tokens, vocab) fp32 transient (round-5 v5e sweep: chunks=1 beat the
    #: 3-chunk auto by ~1 MFU point at the 406M bench shape).
    ce_chunks: Optional[int] = None
    attn_impl: str = "auto"           # auto|xla|flash|ring (see ops/attention)
    #: lax.scan unroll over the layer dimension: >1 lets XLA schedule across
    #: block boundaries (overlap the next layer's weight loads with this
    #: layer's math) at the cost of compile time ∝ unroll
    scan_unroll: int = 1
    # Mixture-of-Experts (0 = dense MLP). Experts shard over the mesh's
    # ``ep`` axis; routing uses GShard/Switch-style dense dispatch einsums
    # (one-hot matmuls — static shapes, MXU-friendly, XLA inserts the
    # all-to-alls from the sharding constraints).
    n_experts: int = 0
    experts_per_token: int = 2
    capacity_factor: float = 1.25
    moe_aux_weight: float = 0.01      # load-balancing loss weight

    # GPT-J-6B shape (reference north star):
    # vocab 50400→50432, seq 2048, d_model 4096, 28 layers, 16 heads

    @property
    def d_ff(self) -> int:
        return 4 * self.d_model

    @property
    def head_dim(self) -> int:
        return self.d_model // self.n_heads


def gpt_init(rng: jax.Array, cfg: GPTConfig) -> dict:
    """Initialize the parameter pytree (fp32 master weights)."""
    k_tok, k_pos, k_blocks, k_head = jax.random.split(rng, 4)
    d, dff, L = cfg.d_model, cfg.d_ff, cfg.n_layers
    init = jax.nn.initializers.normal(0.02)

    def kernel(key, shape, fan_in):
        return jax.random.normal(key, shape, jnp.float32) * (fan_in**-0.5)

    ks = jax.random.split(k_blocks, 5)
    blocks = {
        "ln1": {"scale": jnp.ones((L, d)), "bias": jnp.zeros((L, d))},
        "attn_qkv": {"kernel": kernel(ks[0], (L, d, 3 * d), d), "bias": jnp.zeros((L, 3 * d))},
        "attn_out": {"kernel": kernel(ks[1], (L, d, d), d), "bias": jnp.zeros((L, d))},
        "ln2": {"scale": jnp.ones((L, d)), "bias": jnp.zeros((L, d))},
    }
    if cfg.n_experts > 0:
        E = cfg.n_experts
        blocks["router"] = {"kernel": kernel(ks[4], (L, d, E), d)}
        blocks["moe_in"] = {"kernel": kernel(ks[2], (L, E, d, dff), d)}
        blocks["moe_out"] = {"kernel": kernel(ks[3], (L, E, dff, d), dff)}
    else:
        blocks["mlp_in"] = {"kernel": kernel(ks[2], (L, d, dff), d), "bias": jnp.zeros((L, dff))}
        blocks["mlp_out"] = {"kernel": kernel(ks[3], (L, dff, d), dff), "bias": jnp.zeros((L, d))}
    return {
        "embed": {
            "tokens": init(k_tok, (cfg.vocab_size, d), jnp.float32),
            "pos": init(k_pos, (cfg.seq_len, d), jnp.float32),
        },
        "blocks": blocks,
        "ln_f": {"scale": jnp.ones((d,)), "bias": jnp.zeros((d,))},
        "lm_head": {"kernel": kernel(k_head, (d, cfg.vocab_size), d)},
    }


def _layernorm(x, scale, bias):
    x32 = x.astype(jnp.float32)
    mu = x32.mean(-1, keepdims=True)
    var = x32.var(-1, keepdims=True)
    out = (x32 - mu) * jax.lax.rsqrt(var + 1e-5) * scale + bias
    return out.astype(x.dtype)


def _moe_mlp(cfg: GPTConfig, x, layer, c):
    """Mixture-of-experts MLP with GShard/Switch dense dispatch.

    Routing is all one-hot einsums over static shapes: top-k gate → capacity
    assignment via cumsum → (tokens, E, cap) dispatch tensor → expert matmuls
    on (E, cap, d) — sharded over the ``ep`` mesh axis, so XLA compiles the
    dispatch/combine einsums into all-to-alls over ICI. Over-capacity
    assignments drop (standard). Returns (out, aux_loss).
    """
    from jax.sharding import PartitionSpec as P

    b, s, d = x.shape
    E, k = cfg.n_experts, cfg.experts_per_token
    n = b * s
    cap = max(1, int(cfg.capacity_factor * k * n / E))
    flat = x.reshape(n, d)

    logits = (flat @ layer["router"]["kernel"].astype(x.dtype)).astype(jnp.float32)
    probs = jax.nn.softmax(logits, axis=-1)                     # (n, E)
    gate_w, gate_idx = jax.lax.top_k(probs, k)                  # (n, k)
    gate_w = gate_w / jnp.maximum(gate_w.sum(-1, keepdims=True), 1e-9)

    # (n*k assignments) -> expert one-hot, position within expert via cumsum
    a_idx = gate_idx.reshape(n * k)
    onehot = jax.nn.one_hot(a_idx, E, dtype=jnp.float32)        # (nk, E)
    pos = (jnp.cumsum(onehot, axis=0) - 1.0) * onehot           # (nk, E)
    pos_in_expert = pos.sum(-1)                                 # (nk,)
    keep = (pos_in_expert < cap).astype(jnp.float32)
    disp = onehot * keep[:, None]                               # (nk, E)
    pos_oh = jax.nn.one_hot(pos_in_expert.astype(jnp.int32), cap, dtype=jnp.float32)
    # fold the k slots back into tokens: (n, E, cap) dispatch tensor — a
    # token's top-k experts are distinct, so summing slots never collides.
    # O(n·E·cap), never an (n, n) tensor (GShard's dispatch/combine form).
    disp_t = (disp[:, :, None] * pos_oh[:, None, :]).reshape(n, k, E, cap)
    dispatch = disp_t.sum(axis=1)                               # (n, E, cap)
    combine = (disp_t * gate_w[:, :, None, None]).sum(axis=1)   # (n, E, cap)

    expert_in = jnp.einsum("nec,nd->ecd", dispatch.astype(x.dtype), flat)
    expert_in = c(expert_in, P("ep", None, None))
    h = jax.nn.gelu(
        jnp.einsum("ecd,edf->ecf", expert_in, layer["moe_in"]["kernel"].astype(x.dtype))
    )
    h = c(h, P("ep", None, "tp"))
    expert_out = jnp.einsum("ecf,efd->ecd", h, layer["moe_out"]["kernel"].astype(x.dtype))
    expert_out = c(expert_out, P("ep", None, None))
    out = jnp.einsum("nec,ecd->nd", combine.astype(x.dtype), expert_out).reshape(b, s, d)

    # Switch load-balancing aux: E * sum(frac_tokens_e * mean_prob_e)
    frac = (onehot * keep[:, None]).mean(0)
    mean_prob = probs.mean(0)
    aux = E * jnp.sum(frac * mean_prob) * k
    return out, aux.astype(jnp.float32)


def _block(cfg: GPTConfig, x, layer, mesh=None):
    """One transformer block. ``layer`` = this layer's params (leading L dim
    already indexed away by scan)."""
    from jax.sharding import PartitionSpec as P

    def c(y, spec):
        if mesh is None:
            return y
        from ray_tpu.parallel.sharding import constrain

        return constrain(y, mesh, spec)

    dt = x.dtype
    b, s, d = x.shape
    h, hd = cfg.n_heads, cfg.head_dim

    with jax.named_scope("attn"):
        ln1 = _layernorm(x, layer["ln1"]["scale"], layer["ln1"]["bias"])
        qkv = ln1 @ layer["attn_qkv"]["kernel"].astype(dt) + layer["attn_qkv"]["bias"].astype(dt)
        qkv = checkpoint_name(qkv, "qkv")  # saved only under remat_policy="attn_qkv"
        # seq stays sharded over sp end-to-end (sequence parallelism); sp=1
        # meshes make these the same constraints as before.
        qkv = c(qkv, P(("dp", "fsdp"), "sp", "tp"))

        def split_heads():
            """q, k, v head-major, (b, h, s, hd) each: what every path but
            the one-chip flash kernels wants."""
            return [t.reshape(b, s, h, hd).transpose(0, 2, 1, 3) for t in jnp.split(qkv, 3, axis=-1)]

        def merge_heads(t):
            return t.transpose(0, 2, 1, 3).reshape(b, s, d)

        impl = cfg.attn_impl
        sharded = mesh is not None and mesh.size > 1
        if impl == "ring" and mesh is None:
            raise ValueError(
                "attn_impl='ring' needs a device mesh with an 'sp' axis; pass "
                "mesh= (or use attn_impl='auto', which picks ring only when the "
                "mesh shards sequence)"
            )
        if impl == "ring" or (impl == "auto" and sharded and mesh.shape.get("sp", 1) > 1):
            # sequence sharded over sp: ring attention rotates KV over ICI
            from ray_tpu.ops.ring_attention import ring_attention_sharded

            att = merge_heads(ring_attention_sharded(*split_heads(), mesh))
        else:
            from ray_tpu.ops.attention import auto_impl
            from ray_tpu.ops.flash_attention import (
                flash_attention_packed,
                flash_attention_sharded,
                flash_shardable,
            )

            want_flash = impl == "flash" or (impl == "auto" and auto_impl(s) == "flash")
            if want_flash and not sharded:
                # the kernels read q, k and v as column blocks of the
                # projection's own output and write what ``attn_out`` reads:
                # no split, no head-major copy, forward or backward
                att = flash_attention_packed(qkv, h)
            elif want_flash and s >= 128 and s % 128 == 0 and flash_shardable(b, h, mesh):
                # multi-device pjit: shard_map the Pallas kernel so it runs on
                # each chip's dp/tp shard instead of being replicated (no GSPMD
                # rule for a bare pallas_call)
                att = merge_heads(flash_attention_sharded(*split_heads(), mesh))
            else:
                # XLA's einsum: asked for, or picked by ``auto`` for this
                # shape, or multi-device and not shardable (batch/heads don't
                # divide the mesh: a bare pallas_call would replicate on every
                # chip — the XLA einsum partitions correctly instead)
                att = merge_heads(
                    causal_attention(*split_heads(), impl="xla" if want_flash else impl))
        att = att @ layer["attn_out"]["kernel"].astype(dt) + layer["attn_out"]["bias"].astype(dt)
        x = x + c(att, P(("dp", "fsdp"), "sp", None))

    with jax.named_scope("mlp"):
        ln2 = _layernorm(x, layer["ln2"]["scale"], layer["ln2"]["bias"])
        if cfg.n_experts > 0:
            out, aux = _moe_mlp(cfg, ln2, layer, c)
        else:
            hmid = jax.nn.gelu(ln2 @ layer["mlp_in"]["kernel"].astype(dt) + layer["mlp_in"]["bias"].astype(dt))
            hmid = checkpoint_name(hmid, "mlp_mid")
            hmid = c(hmid, P(("dp", "fsdp"), "sp", "tp"))
            out = hmid @ layer["mlp_out"]["kernel"].astype(dt) + layer["mlp_out"]["bias"].astype(dt)
            aux = jnp.float32(0.0)
    return x + c(out, P(("dp", "fsdp"), "sp", None)), aux


_REMAT_POLICIES = {
    "full": lambda: None,
    "dots": lambda: jax.checkpoint_policies.checkpoint_dots,
    # "attn" keeps the flash kernel's out+lse (tagged inside _flash_core_fwd)
    # so the backward's rematerialization never re-runs the attention kernel
    # — everything else (layernorms, qkv/mlp matmuls) recomputes as usual.
    # (On the non-flash XLA fallback there is nothing tagged, so these
    # degrade gracefully to full remat.)
    "attn": lambda: jax.checkpoint_policies.save_only_these_names(
        "flash_out", "flash_lse"
    ),
    # "attn_qkv" additionally saves the qkv projection ((b,s,3d) per layer):
    # the backward then recomputes only layernorms + the cheap elementwise
    # chain, not the qkv matmul feeding the attention VJP
    "attn_qkv": lambda: jax.checkpoint_policies.save_only_these_names(
        "flash_out", "flash_lse", "qkv"
    ),
    "big": lambda: jax.checkpoint_policies.save_only_these_names(
        "flash_out", "flash_lse", "mlp_mid"
    ),
}


def gpt_hidden(cfg: GPTConfig, params: dict, tokens: jax.Array, mesh=None):
    """tokens (batch, seq) int32 → (final hidden (batch, seq, d_model) in the
    activation dtype, mean MoE aux loss). The lm head is applied by the
    caller — gpt_forward materializes logits; gpt_loss feeds the hidden to
    the blockwise fused cross-entropy instead."""
    dt = jnp.dtype(cfg.dtype)
    b, s = tokens.shape
    # gather fp32 rows THEN cast: casting the whole (vocab, d) table first
    # would stream 50k rows through the VPU to use 24k
    x = params["embed"]["tokens"][tokens].astype(dt)
    x = x + params["embed"]["pos"][:s].astype(dt)

    def block(carry, layer):
        y, aux = _block(cfg, carry, layer, mesh)
        return y, aux

    if cfg.remat:
        if cfg.remat_policy not in _REMAT_POLICIES:
            raise ValueError(
                f"remat_policy must be one of {sorted(_REMAT_POLICIES)}, "
                f"got {cfg.remat_policy!r}"
            )
        policy = _REMAT_POLICIES[cfg.remat_policy]()
        block = jax.checkpoint(block, prevent_cse=False, policy=policy)
    x, auxes = jax.lax.scan(block, x, params["blocks"], unroll=cfg.scan_unroll)

    x = _layernorm(x, params["ln_f"]["scale"], params["ln_f"]["bias"])
    return x, auxes.mean()


def gpt_forward(
    cfg: GPTConfig, params: dict, tokens: jax.Array, mesh=None, return_aux: bool = False
):
    """tokens (batch, seq) int32 → logits (batch, seq, vocab) fp32.

    ``return_aux=True`` also returns the mean MoE load-balancing loss."""
    x, aux = gpt_hidden(cfg, params, tokens, mesh)
    logits = x.astype(jnp.float32) @ params["lm_head"]["kernel"]
    if return_aux:
        return logits, aux
    return logits


def gpt_loss(cfg: GPTConfig, params: dict, tokens: jax.Array, mesh=None) -> jax.Array:
    """Next-token cross-entropy, mean over (batch, seq-1); MoE configs add
    the weighted load-balancing aux loss.

    With ``cfg.fused_loss`` (default) the loss never materializes the
    (tokens, vocab) logits: ``ops.fused_ce`` streams vocab chunks through
    the MXU in the activation dtype (see its module docstring for the HBM
    arithmetic — ~6.6 GB saved at the 406M bench shape)."""
    hidden, aux = gpt_hidden(cfg, params, tokens[:, :-1], mesh)
    targets = tokens[:, 1:]
    if cfg.fused_loss:
        from ray_tpu.ops.fused_ce import fused_softmax_cross_entropy

        b, s, d = hidden.shape
        with jax.named_scope("ce"):
            losses = fused_softmax_cross_entropy(
                hidden.reshape(b * s, d),
                params["lm_head"]["kernel"],
                targets.reshape(-1).astype(jnp.int32),
                cfg.ce_chunks,
            )
            loss = losses.mean()
    else:
        with jax.named_scope("ce"):
            logits = hidden.astype(jnp.float32) @ params["lm_head"]["kernel"]
            logp = jax.nn.log_softmax(logits, axis=-1)
            ll = jnp.take_along_axis(logp, targets[..., None], axis=-1)[..., 0]
            loss = -ll.mean()
    if cfg.n_experts > 0:
        loss = loss + cfg.moe_aux_weight * aux
    return loss


# ---------------------------------------------------------------------------
# KV-cache decode (inference path; sampling shared with models.sampling)
# ---------------------------------------------------------------------------


def gpt_decode(
    cfg: GPTConfig,
    params: dict,
    prompt: jax.Array,
    n_new: int,
    *,
    key: Optional[jax.Array] = None,
    temperature=0.0,
    top_k=0,
    top_p=1.0,
) -> jax.Array:
    """Decode ``n_new`` tokens after ``prompt`` (b, s0) int32 →
    (b, s0 + n_new), KV-cached with static shapes (same discipline as
    ``models.gptj.gptj_decode``: one prefill forward capturing per-layer
    k/v, then a ``lax.fori_loop`` of single-position steps). Greedy by
    default; with a PRNG ``key``, per-token temperature/top-k/top-p via
    ``models.sampling.sample_tokens`` (scalars or per-row arrays).

    Dense blocks only (``n_experts == 0``); the learned positional table
    caps ``s0 + n_new`` at ``cfg.seq_len``."""
    if cfg.n_experts > 0:
        raise NotImplementedError("gpt_decode supports dense (non-MoE) configs only")
    dt = jnp.dtype(cfg.dtype)
    b, s0 = prompt.shape
    nh, hd = cfg.n_heads, cfg.head_dim
    max_len = s0 + n_new
    if max_len > cfg.seq_len:
        raise ValueError(
            f"prompt ({s0}) + n_new ({n_new}) exceeds the positional table "
            f"(seq_len={cfg.seq_len})"
        )

    def pick(logits, step_idx):
        if key is None:
            return jnp.argmax(logits, axis=-1).astype(jnp.int32)
        from ray_tpu.models.sampling import sample_tokens

        return sample_tokens(
            logits, jax.random.fold_in(key, step_idx), temperature, top_k, top_p
        )

    def heads(t, s):
        return t.reshape(b, s, nh, hd).transpose(0, 2, 1, 3)

    # ---- prefill: normal stacked forward, capturing per-layer k/v
    x = params["embed"]["tokens"][prompt].astype(dt)
    x = x + params["embed"]["pos"][:s0].astype(dt)

    def prefill_block(carry, layer):
        ln1 = _layernorm(carry, layer["ln1"]["scale"], layer["ln1"]["bias"])
        qkv = ln1 @ layer["attn_qkv"]["kernel"].astype(dt) + layer["attn_qkv"]["bias"].astype(dt)
        q, k, v = jnp.split(qkv, 3, axis=-1)
        att = causal_attention(heads(q, s0), heads(k, s0), heads(v, s0), impl="xla")
        att = att.transpose(0, 2, 1, 3).reshape(b, s0, cfg.d_model)
        att = att @ layer["attn_out"]["kernel"].astype(dt) + layer["attn_out"]["bias"].astype(dt)
        h = carry + att
        ln2 = _layernorm(h, layer["ln2"]["scale"], layer["ln2"]["bias"])
        mid = jax.nn.gelu(
            ln2 @ layer["mlp_in"]["kernel"].astype(dt) + layer["mlp_in"]["bias"].astype(dt)
        )
        mlp = mid @ layer["mlp_out"]["kernel"].astype(dt) + layer["mlp_out"]["bias"].astype(dt)
        pad = jnp.zeros((b, nh, n_new, hd), dt)
        kc = jnp.concatenate([heads(k, s0).astype(dt), pad], axis=2)
        vc = jnp.concatenate([heads(v, s0).astype(dt), pad], axis=2)
        return h + mlp, (kc, vc)

    x, (k_caches, v_caches) = jax.lax.scan(prefill_block, x, params["blocks"])
    hlast = _layernorm(x[:, -1], params["ln_f"]["scale"], params["ln_f"]["bias"])
    logits = hlast.astype(jnp.float32) @ params["lm_head"]["kernel"]
    first_new = pick(logits, 0)  # (b,)

    tokens = jnp.concatenate([prompt, jnp.zeros((b, n_new), jnp.int32)], axis=1)
    tokens = jax.lax.dynamic_update_slice(tokens, first_new[:, None], (0, s0))

    def step(i, carry):
        tokens, k_caches, v_caches = carry
        pos = s0 + i  # position of the token being FED
        tok = jax.lax.dynamic_slice(tokens, (0, pos), (b, 1))[:, 0]
        x1 = params["embed"]["tokens"][tok].astype(dt)  # (b, d)
        x1 = x1 + jax.lax.dynamic_slice(
            params["embed"]["pos"], (pos, 0), (1, cfg.d_model)
        ).astype(dt)

        def one_layer(carry1, inputs):
            x1 = carry1
            layer, kc, vc = inputs
            ln1 = _layernorm(x1, layer["ln1"]["scale"], layer["ln1"]["bias"])
            qkv = ln1 @ layer["attn_qkv"]["kernel"].astype(dt) + layer["attn_qkv"]["bias"].astype(dt)
            q, k, v = jnp.split(qkv, 3, axis=-1)  # (b, d) each
            q = q.reshape(b, nh, hd)
            k = k.reshape(b, nh, 1, hd).astype(dt)
            v = v.reshape(b, nh, 1, hd).astype(dt)
            kc = jax.lax.dynamic_update_slice(kc, k, (0, 0, pos, 0))
            vc = jax.lax.dynamic_update_slice(vc, v, (0, 0, pos, 0))
            from ray_tpu.models.gptj import _attend_cached

            att = _attend_cached(q, kc, vc, pos + 1).astype(dt)
            att = att.reshape(b, cfg.d_model) @ layer["attn_out"]["kernel"].astype(dt)
            att = att + layer["attn_out"]["bias"].astype(dt)
            h = x1 + att
            ln2 = _layernorm(h, layer["ln2"]["scale"], layer["ln2"]["bias"])
            mid = jax.nn.gelu(
                ln2 @ layer["mlp_in"]["kernel"].astype(dt)
                + layer["mlp_in"]["bias"].astype(dt)
            )
            mlp = mid @ layer["mlp_out"]["kernel"].astype(dt) + layer["mlp_out"]["bias"].astype(dt)
            return h + mlp, (kc, vc)

        x1, (k_caches, v_caches) = jax.lax.scan(
            one_layer, x1, (params["blocks"], k_caches, v_caches)
        )
        h1 = _layernorm(x1, params["ln_f"]["scale"], params["ln_f"]["bias"])
        logits = h1.astype(jnp.float32) @ params["lm_head"]["kernel"]
        nxt = pick(logits, i + 1)
        tokens = jax.lax.dynamic_update_slice(tokens, nxt[:, None], (0, pos + 1))
        return tokens, k_caches, v_caches

    tokens, _, _ = jax.lax.fori_loop(0, n_new - 1, step, (tokens, k_caches, v_caches))
    return tokens
