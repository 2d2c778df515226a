"""Configurable decoder-only LM transformer covering the assigned archs:

  granite-3-2b / gemma3-27b / command-r-plus-104b  (dense; GQA; gemma3 adds
      5:1 local:global sliding-window attention)
  qwen2-moe-a2.7b   (shared + routed experts, top-4)
  deepseek-v3-671b  (MLA latent attention, 1 shared + 256 routed top-8, MTP)

Scale-critical choices (DESIGN.md §5):
  * ``lax.scan`` over stacked layer params (+ optional per-layer remat) —
    HLO size independent of depth;
  * flash-style attention: scan over query blocks, rematerialized block
    bodies — no S×S HBM residency at 32k (Pallas kernel is the TPU fast
    path, this is the portable lowering);
  * MoE as a *manual* ``shard_map`` over ("data","model"): experts live
    on the "model" axis, each data row routes its own tokens locally,
    expert weights are FSDP-stored (D over "data") and all-gathered per
    layer; one psum over "model" combines expert outputs.  No GSPMD
    surprises on the data-dependent dispatch;
  * chunked cross-entropy: logits are never materialized [B,S,V] —
    scan over sequence chunks with vocab TP-sharded.
"""
from __future__ import annotations

import dataclasses
import math
from typing import Any, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, PartitionSpec as P

from repro.parallel.sharding import ShardingRules, batch_axes


@dataclasses.dataclass(frozen=True)
class TransformerConfig:
    name: str = "lm"
    n_layers: int = 4
    d_model: int = 256
    n_heads: int = 4
    n_kv_heads: int = 4
    d_head: int = 64
    d_ff: int = 1024
    vocab_size: int = 1024
    # --- MoE ---
    moe: bool = False
    n_experts: int = 0
    n_experts_padded: int = 0        # storage padding so E % model_axis == 0
    n_shared_experts: int = 0
    top_k: int = 0
    moe_d_ff: int = 0
    first_dense_layers: int = 0      # deepseek: leading dense layers
    capacity_factor: float = 1.25

    @property
    def e_pad(self):
        return self.n_experts_padded or self.n_experts
    # --- MLA (deepseek) ---
    mla: bool = False
    q_lora_rank: int = 0
    kv_lora_rank: int = 0
    qk_nope_dim: int = 0
    qk_rope_dim: int = 0
    v_head_dim: int = 0
    # --- attention pattern ---
    sliding_window: int = 0          # 0 = full attention everywhere
    global_every: int = 0            # gemma3: layer i is global iff (i+1) % global_every == 0
    # --- misc ---
    tie_embeddings: bool = True
    rope_theta: float = 10000.0
    norm_eps: float = 1e-6
    mtp: bool = False                # extra next-next-token head (deepseek)
    dtype: Any = jnp.bfloat16
    remat: bool = True
    # "full": recompute everything in backward; "dots": keep un-batched
    # matmul outputs (§Perf H3c — trades HBM headroom for fewer backward
    # recomputes and re-gathers)
    remat_policy: str = "full"
    q_block: int = 512               # flash q-block size
    use_flash: bool = True

    @property
    def qk_dim(self):
        return (self.qk_nope_dim + self.qk_rope_dim) if self.mla else self.d_head

    @property
    def v_dim(self):
        return self.v_head_dim if self.mla else self.d_head

    def n_params(self) -> int:
        """Approximate parameter count (for roofline MODEL_FLOPS)."""
        c = self
        emb = c.vocab_size * c.d_model * (1 if c.tie_embeddings else 2)
        if c.mla:
            attn = (c.d_model * c.q_lora_rank
                    + c.q_lora_rank * c.n_heads * c.qk_dim
                    + c.d_model * (c.kv_lora_rank + c.qk_rope_dim)
                    + c.kv_lora_rank * c.n_heads * (c.qk_nope_dim + c.v_dim)
                    + c.n_heads * c.v_dim * c.d_model)
        else:
            attn = c.d_model * (c.n_heads + 2 * c.n_kv_heads) * c.d_head \
                + c.n_heads * c.d_head * c.d_model
        dense_ffn = 3 * c.d_model * c.d_ff
        moe_ffn = 3 * c.d_model * c.moe_d_ff * (c.n_experts
                                                + c.n_shared_experts)
        n_moe = (c.n_layers - c.first_dense_layers) if c.moe else 0
        n_dense = c.n_layers - n_moe
        return emb + c.n_layers * attn + n_dense * dense_ffn + n_moe * moe_ffn

    def n_active_params(self) -> int:
        """Active params per token (MoE: only routed top-k + shared)."""
        c = self
        if not c.moe:
            return self.n_params()
        emb = c.vocab_size * c.d_model * (1 if c.tie_embeddings else 2)
        if c.mla:
            attn = (c.d_model * c.q_lora_rank
                    + c.q_lora_rank * c.n_heads * c.qk_dim
                    + c.d_model * (c.kv_lora_rank + c.qk_rope_dim)
                    + c.kv_lora_rank * c.n_heads * (c.qk_nope_dim + c.v_dim)
                    + c.n_heads * c.v_dim * c.d_model)
        else:
            attn = c.d_model * (c.n_heads + 2 * c.n_kv_heads) * c.d_head \
                + c.n_heads * c.d_head * c.d_model
        dense_ffn = 3 * c.d_model * c.d_ff
        act_moe = 3 * c.d_model * c.moe_d_ff * (c.top_k + c.n_shared_experts)
        n_moe = c.n_layers - c.first_dense_layers
        return emb + c.n_layers * attn \
            + c.first_dense_layers * dense_ffn + n_moe * act_moe


# ---------------------------------------------------------------------------
# Parameter init
# ---------------------------------------------------------------------------

def _dense_layer_shapes(c: TransformerConfig, ffn_dense: bool):
    """Shapes of one layer's params. ffn_dense: dense FFN vs MoE FFN."""
    s = {"ln1": (c.d_model,), "ln2": (c.d_model,)}
    if c.mla:
        s.update({
            "wq_a": (c.d_model, c.q_lora_rank),
            "q_ln": (c.q_lora_rank,),
            "wq_b": (c.q_lora_rank, c.n_heads * c.qk_dim),
            "wkv_a": (c.d_model, c.kv_lora_rank + c.qk_rope_dim),
            "kv_ln": (c.kv_lora_rank,),
            "wkv_b": (c.kv_lora_rank, c.n_heads * (c.qk_nope_dim + c.v_dim)),
            "wo": (c.n_heads * c.v_dim, c.d_model),
        })
    else:
        s.update({
            "wq": (c.d_model, c.n_heads * c.d_head),
            "wk": (c.d_model, c.n_kv_heads * c.d_head),
            "wv": (c.d_model, c.n_kv_heads * c.d_head),
            "wo": (c.n_heads * c.d_head, c.d_model),
        })
    if ffn_dense:
        s.update({"w_gate": (c.d_model, c.d_ff),
                  "w_up": (c.d_model, c.d_ff),
                  "w_down": (c.d_ff, c.d_model)})
    else:
        s.update({
            "router": (c.d_model, c.n_experts),
            "we_gate": (c.e_pad, c.d_model, c.moe_d_ff),
            "we_up": (c.e_pad, c.d_model, c.moe_d_ff),
            "we_down": (c.e_pad, c.moe_d_ff, c.d_model),
        })
        if c.n_shared_experts:
            f = c.moe_d_ff * c.n_shared_experts
            s.update({"ws_gate": (c.d_model, f), "ws_up": (c.d_model, f),
                      "ws_down": (f, c.d_model)})
    return s


def param_shapes(c: TransformerConfig):
    """Abstract shapes of the full parameter pytree (stacked layers)."""
    n_moe = (c.n_layers - c.first_dense_layers) if c.moe else 0
    n_dense = c.n_layers - n_moe
    shapes = {"embed": (c.vocab_size, c.d_model), "final_ln": (c.d_model,)}
    if not c.tie_embeddings:
        shapes["unembed"] = (c.d_model, c.vocab_size)
    if n_dense:
        shapes["dense_layers"] = {k: (n_dense,) + v for k, v in
                                  _dense_layer_shapes(c, True).items()}
    if n_moe:
        shapes["moe_layers"] = {k: (n_moe,) + v for k, v in
                                _dense_layer_shapes(c, False).items()}
    if c.mtp:
        shapes["mtp_proj"] = (2 * c.d_model, c.d_model)
        shapes["mtp_ln"] = (c.d_model,)
    return shapes


def init_params(c: TransformerConfig, key):
    shapes = param_shapes(c)
    flat, treedef = jax.tree_util.tree_flatten_with_path(shapes,
        is_leaf=lambda x: isinstance(x, tuple))
    keys = jax.random.split(key, len(flat))
    leaves = []
    for (path, shape), k in zip(flat, keys):
        name = path[-1].key if hasattr(path[-1], "key") else str(path[-1])
        if name.startswith(("ln", "final_ln", "q_ln", "kv_ln", "mtp_ln")) \
                or name in ("ln1", "ln2"):
            leaves.append(jnp.ones(shape, c.dtype))
        else:
            scale = 0.02
            leaves.append((jax.random.normal(k, shape, jnp.float32)
                           * scale).astype(c.dtype))
    return jax.tree_util.tree_unflatten(treedef, leaves)


def abstract_params(c: TransformerConfig):
    """ShapeDtypeStructs for the param pytree (dry-run, no allocation)."""
    return jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(s, c.dtype),
        param_shapes(c), is_leaf=lambda x: isinstance(x, tuple))


# ---------------------------------------------------------------------------
# Sharding specs
# ---------------------------------------------------------------------------

_TP_DIMS = {
    # layer params: (stacked) dim index carrying the TP axis (post-stack)
    "wq": 2, "wk": 2, "wv": 2, "wo": 1,
    "wq_b": 2, "wkv_b": 2,
    "w_gate": 2, "w_up": 2, "w_down": 1,
    "ws_gate": 2, "ws_up": 2, "ws_down": 1,
    "we_gate": 1, "we_up": 1, "we_down": 1,   # experts over model axis
    "router": None, "wq_a": None, "wkv_a": None,
    "ln1": None, "ln2": None, "q_ln": None, "kv_ln": None,
}
_FSDP_DIMS = {
    "wq": 1, "wk": 1, "wv": 1, "wo": 2,
    "wq_a": 1, "wq_b": 1, "wkv_a": 1, "wkv_b": 1,
    "w_gate": 1, "w_up": 1, "w_down": 2,
    "ws_gate": 1, "ws_up": 1, "ws_down": 2,
    "we_gate": 2, "we_up": 2, "we_down": 3,   # D dim over data (gathered in MoE blk)
    "router": None,
    "ln1": None, "ln2": None, "q_ln": None, "kv_ln": None,
}


def param_pspecs(c: TransformerConfig, mesh: Mesh, rules: ShardingRules):
    """PartitionSpec pytree matching param_shapes(c).

    TP over attention heads only when n_kv_heads divides the model axis
    (keeps the GQA head reshape shard-aligned; otherwise attention params
    are FSDP-only — e.g. command-r-plus kv=8 on a 16-way model axis).
    """
    msize = dict(zip(mesh.axis_names, mesh.devices.shape))
    tp = rules.tensor if rules.tensor in mesh.axis_names else None
    fsa = rules.fsdp_axes(mesh)
    fsn = rules.fsdp_size(mesh)
    kv_tp_ok = tp is not None and (c.mla or c.n_kv_heads % msize[tp] == 0)

    def spec_for(name, shape):
        axes = [None] * len(shape)
        tpd = _TP_DIMS.get(name)
        if name in ("wq", "wk", "wv", "wo") and not kv_tp_ok:
            tpd = None
        if tp and tpd is not None and tpd < len(shape) \
                and shape[tpd] % msize[tp] == 0:
            axes[tpd] = tp
        else:
            tpd = None
        fsd = _FSDP_DIMS.get(name)
        if fsa and fsd is not None and fsd < len(shape) and fsd != tpd \
                and shape[fsd] % fsn == 0:
            axes[fsd] = fsa
        return P(*axes)

    def build(node, name=""):
        if isinstance(node, dict):
            return {k: build(v, k) for k, v in node.items()}
        shape = node
        if name == "embed":
            axes = [None, None]
            if tp and shape[0] % msize[tp] == 0:
                axes[0] = tp
            if fsa and shape[1] % fsn == 0:
                axes[1] = fsa
            return P(*axes)
        if name == "unembed":
            axes = [None, None]
            if tp and shape[1] % msize[tp] == 0:
                axes[1] = tp
            if fsa and shape[0] % fsn == 0:
                axes[0] = fsa
            return P(*axes)
        if name in ("final_ln", "mtp_ln"):
            return P(None)
        if name == "mtp_proj":
            return spec_for("wo", shape)
        return spec_for(name, shape)

    return build(param_shapes(c))


# ---------------------------------------------------------------------------
# Building blocks
# ---------------------------------------------------------------------------

def _constrain(x, mesh, spec):
    """Activation sharding constraint (no-op without a mesh).

    GSPMD does not reliably propagate batch sharding through gathers
    (embedding lookups) and long scan chains — without these anchors the
    compiler replicates activations (measured: granite train_4k peak
    1458 GiB/device → 4.9 GiB/device with constraints)."""
    if mesh is None:
        return x
    from jax.sharding import NamedSharding
    return jax.lax.with_sharding_constraint(x, NamedSharding(mesh, spec))


def _bspec(mesh, rules, batch: int, extra_dims: int):
    ax = batch_axes(mesh, rules)
    sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
    n = int(np.prod([sizes[a] for a in ax])) if ax else 1
    first = ax if (n > 1 and batch % n == 0) else None
    return P(first, *([None] * extra_dims))


def _hspec(mesh, rules, batch: int, seq: int):
    """Residual-stream sharding [B, S, D]: batch over (pod,data) and —
    sequence parallelism — S over the tensor axis.  SP keeps the
    remat-saved per-layer activations 1/TP-sized; attention/MoE gather S
    transiently inside the (rematted) layer."""
    b = _bspec(mesh, rules, batch, 0)[0]
    sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
    tp = rules.tensor if rules.tensor in mesh.axis_names else None
    s_ax = tp if (tp and seq > 1 and seq % sizes[tp] == 0) else None
    return P(b, s_ax, None)


def rms_norm(x, w, eps):
    var = jnp.mean(jnp.square(x.astype(jnp.float32)), axis=-1, keepdims=True)
    return (x.astype(jnp.float32) * jax.lax.rsqrt(var + eps)).astype(x.dtype) \
        * w


def rope(x, positions, theta, dims: Optional[int] = None):
    """Rotary embedding over the last ``dims`` features (default: all)."""
    d = x.shape[-1] if dims is None else dims
    half = d // 2
    freqs = 1.0 / (theta ** (jnp.arange(0, half, dtype=jnp.float32) / half))
    ang = positions[..., None].astype(jnp.float32) * freqs      # [..., S, half]
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    rot, keep = x[..., :d], x[..., d:]
    x1, x2 = rot[..., :half], rot[..., half:]
    cos = cos[:, :, None, :] if rot.ndim == 4 else cos
    sin = sin[:, :, None, :] if rot.ndim == 4 else sin
    out = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)
    return jnp.concatenate([out.astype(x.dtype), keep], axis=-1)


def _attend_block(q, k, v, qpos, kpos, window, scale):
    """One (q-block × full-K) attention with causal/sliding mask.

    q: [B,Cq,H,dq] k: [B,S,KV,dq] v: [B,S,KV,dv] → [B,Cq,H,dv]
    """
    b, cq, h, dq = q.shape
    s, kv = k.shape[1], k.shape[2]
    groups = h // kv
    qg = q.reshape(b, cq, kv, groups, dq)
    scores = jnp.einsum("bqkgd,bskd->bkgqs", qg, k).astype(jnp.float32) * scale
    mask = (kpos[None, :] <= qpos[:, None]) \
        & (kpos[None, :] > qpos[:, None] - window)
    scores = jnp.where(mask[None, None, None, :, :], scores, -1e30)
    probs = jax.nn.softmax(scores, axis=-1).astype(v.dtype)
    out = jnp.einsum("bkgqs,bskd->bqkgd", probs, v)
    return out.reshape(b, cq, h, v.shape[-1])


def flash_attention(q, k, v, q_offset, window, scale, q_block, use_remat=True):
    """Scan over q blocks; each block attends to all K with masking.

    q: [B,S,H,dq]; k,v: [B,S,KV,d*]. window: traced or static scalar
    (big value = full causal). Returns [B,S,H,dv].
    """
    b, s, h, dq = q.shape
    if s % q_block != 0:
        nq, qb = 1, s          # short/ragged sequence: one block
    else:
        nq, qb = s // q_block, q_block
    kpos = jnp.arange(k.shape[1])

    def body(_, qblk_and_start):
        qblk, start = qblk_and_start
        qpos = q_offset + start + jnp.arange(qb)
        fn = _attend_block
        if use_remat:
            fn = jax.checkpoint(_attend_block,
                                static_argnums=())
        return None, fn(qblk, k, v, qpos, kpos, window, scale)

    qs = q.reshape(b, nq, qb, h, dq).transpose(1, 0, 2, 3, 4)
    starts = jnp.arange(nq) * qb
    _, outs = jax.lax.scan(body, None, (qs, starts))
    return outs.transpose(1, 0, 2, 3, 4).reshape(b, s, h, -1)


def _head_spec(mesh, rules, batch, n_heads):
    """[B, S, H, D] attention tensors: batch over (pod,data), HEADS over
    'model', S gathered — §Perf H3: with SP residuals, gathering the
    per-shard head slice over S costs TP× less than gathering all heads."""
    b = _bspec(mesh, rules, batch, 0)[0]
    sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
    tp = rules.tensor if rules.tensor in mesh.axis_names else None
    h_ax = tp if (tp and n_heads % sizes[tp] == 0) else None
    return P(b, None, h_ax, None)


def attention_dense(x, layer, c: TransformerConfig, positions, window,
                    kv_cache=None, cache_pos=None, mesh=None, rules=None):
    """GQA attention. Returns (out, new_kv) where kv = (k_all, v_all)."""
    b, s, _ = x.shape
    q = (x @ layer["wq"]).reshape(b, s, c.n_heads, c.d_head)
    k = (x @ layer["wk"]).reshape(b, s, c.n_kv_heads, c.d_head)
    v = (x @ layer["wv"]).reshape(b, s, c.n_kv_heads, c.d_head)
    q = rope(q, positions, c.rope_theta)
    k = rope(k, positions, c.rope_theta)
    if mesh is not None and s > 1:
        q = _constrain(q, mesh, _head_spec(mesh, rules, b, c.n_heads))
        kvs = _head_spec(mesh, rules, b, c.n_kv_heads)
        k = _constrain(k, mesh, kvs)
        v = _constrain(v, mesh, kvs)
    scale = 1.0 / math.sqrt(c.d_head)
    if kv_cache is not None:
        ck, cv = kv_cache
        ck = jax.lax.dynamic_update_slice(ck, k.astype(ck.dtype),
                                          (0, cache_pos, 0, 0))
        cv = jax.lax.dynamic_update_slice(cv, v.astype(cv.dtype),
                                          (0, cache_pos, 0, 0))
        if s > 1:
            # prefill: flash attention over the in-context K/V; the cache
            # write above is independent of the attention compute.
            out = flash_attention(q, k, v, 0, window, scale, c.q_block,
                                  use_remat=True)
        else:
            # decode: one query row against the whole cache
            kpos = jnp.arange(ck.shape[1])
            qpos = positions[0]                       # [1] (uniform batch)
            qg = q.reshape(b, s, c.n_kv_heads, c.n_heads // c.n_kv_heads,
                           c.d_head)
            scores = jnp.einsum(
                "bqkgd,bskd->bkgqs", qg,
                ck.astype(q.dtype)).astype(jnp.float32) * scale
            mask = (kpos[None, :] <= qpos[:, None]) \
                & (kpos[None, :] > qpos[:, None] - window)
            scores = jnp.where(mask[None, None, None, :, :], scores, -1e30)
            probs = jax.nn.softmax(scores, axis=-1).astype(x.dtype)
            out = jnp.einsum("bkgqs,bskd->bqkgd", probs, cv.astype(x.dtype))
        out = out.reshape(b, s, c.n_heads * c.d_head)
        return out @ layer["wo"], (ck, cv)
    out = flash_attention(q, k, v, 0, window, scale, c.q_block,
                          use_remat=True)
    out = out.reshape(b, s, c.n_heads * c.d_head)
    return out @ layer["wo"], None


def attention_mla(x, layer, c: TransformerConfig, positions, window,
                  kv_cache=None, cache_pos=None, mesh=None, rules=None):
    """DeepSeek-style Multi-head Latent Attention.

    Cache stores only the compressed latent (kv_lora_rank) + rope key —
    the MLA memory win.  Decode uses the absorbed-matmul path (scores in
    latent space); train/prefill expands per-head keys/values.

    §Perf H3: under sequence parallelism the cross-shard gather happens
    on the COMPRESSED latent (r+dr dims ≈ 0.14 GB bf16/layer) — the
    per-head K/V expansion runs after, locally, for the shard's heads
    only.  Baseline (expanded-K gather) moved 3 GB f32/layer × 4.
    """
    b, s, _ = x.shape
    h, dn, dr, dv = c.n_heads, c.qk_nope_dim, c.qk_rope_dim, c.v_dim
    r = c.kv_lora_rank
    q_lat = rms_norm(x @ layer["wq_a"], layer["q_ln"], c.norm_eps)
    q = (q_lat @ layer["wq_b"]).reshape(b, s, h, dn + dr)
    q_nope, q_rope = q[..., :dn], q[..., dn:]
    q_rope = rope(q_rope, positions, c.rope_theta)

    kv_a = x @ layer["wkv_a"]                        # [b,s,r+dr]
    c_kv = rms_norm(kv_a[..., :r], layer["kv_ln"], c.norm_eps)
    k_rope = rope(kv_a[..., None, r:], positions, c.rope_theta)  # [b,s,1,dr]
    if mesh is not None and s > 1:
        bspec = _bspec(mesh, rules, b, 0)[0]
        # gather S on the latent only; q/k/v stay head-sharded
        c_kv = _constrain(c_kv, mesh, P(bspec, None, None))
        k_rope = _constrain(k_rope, mesh, P(bspec, None, None, None))
        hs = _head_spec(mesh, rules, b, h)
        q_nope = _constrain(q_nope, mesh, hs)
        q_rope = _constrain(q_rope, mesh, hs)

    wkv_b = layer["wkv_b"].reshape(r, h, dn + dv)
    w_k = wkv_b[..., :dn]                            # [r,h,dn]
    w_v = wkv_b[..., dn:]                            # [r,h,dv]
    scale = 1.0 / math.sqrt(dn + dr)

    if kv_cache is not None:
        cl, cr = kv_cache                            # [b,S,r], [b,S,dr]
        cl = jax.lax.dynamic_update_slice(cl, c_kv.astype(cl.dtype),
                                          (0, cache_pos, 0))
        cr = jax.lax.dynamic_update_slice(
            cr, k_rope[:, :, 0, :].astype(cr.dtype), (0, cache_pos, 0))
        if s > 1:
            # prefill: expand and run flash over the in-context K/V
            k_nope = jnp.einsum("bsr,rhd->bshd", c_kv, w_k)
            v = jnp.einsum("bsr,rhd->bshd", c_kv, w_v)
            k = jnp.concatenate(
                [k_nope, jnp.broadcast_to(k_rope, (b, s, h, dr))], axis=-1)
            q_full = jnp.concatenate([q_nope, q_rope], axis=-1)
            out = flash_attention(q_full, k, v, 0, window, scale, c.q_block,
                                  use_remat=True)
            out = out.reshape(b, s, h * dv)
            return out @ layer["wo"], (cl, cr)
        # decode: absorbed path — q_nope projected into latent space, the
        # per-head K/V expansion never materializes (the MLA decode win).
        q_lat_abs = jnp.einsum("bqhd,rhd->bqhr", q_nope, w_k)
        scores = (jnp.einsum("bqhr,bsr->bhqs", q_lat_abs, cl.astype(q.dtype))
                  + jnp.einsum("bqhd,bsd->bhqs", q_rope,
                               cr.astype(q.dtype))).astype(jnp.float32) * scale
        kpos = jnp.arange(cl.shape[1])
        qpos = positions[0]                          # [1] (uniform batch)
        mask = (kpos[None, :] <= qpos[:, None]) \
            & (kpos[None, :] > qpos[:, None] - window)
        scores = jnp.where(mask[None, None, :, :], scores, -1e30)
        probs = jax.nn.softmax(scores, axis=-1).astype(x.dtype)
        out_lat = jnp.einsum("bhqs,bsr->bqhr", probs, cl.astype(x.dtype))
        out = jnp.einsum("bqhr,rhd->bqhd", out_lat, w_v)
        out = out.reshape(b, s, h * dv)
        return out @ layer["wo"], (cl, cr)

    # train/prefill: expand keys/values per head
    k_nope = jnp.einsum("bsr,rhd->bshd", c_kv, w_k)
    v = jnp.einsum("bsr,rhd->bshd", c_kv, w_v)
    k = jnp.concatenate([k_nope, jnp.broadcast_to(k_rope, (b, s, h, dr))],
                        axis=-1)
    q_full = jnp.concatenate([q_nope, q_rope], axis=-1)
    out = flash_attention(q_full, k, v, 0, window, scale, c.q_block,
                          use_remat=True)
    out = out.reshape(b, s, h * dv)
    return out @ layer["wo"], None


def ffn_dense(x, layer):
    return (jax.nn.silu(x @ layer["w_gate"]) * (x @ layer["w_up"])) \
        @ layer["w_down"]


def moe_block(x, layer, c: TransformerConfig, mesh: Optional[Mesh],
              rules: Optional[ShardingRules]):
    """Routed top-k MoE with shared experts.

    With a mesh: manual shard_map over ("data","model") — see module
    docstring.  Without a mesh (smoke tests): single-device same math.
    """
    b, s, d = x.shape
    xf = x.reshape(b * s, d)

    if mesh is None or rules is None or \
            rules.tensor not in getattr(mesh, "axis_names", ()):
        out = _moe_local(xf, layer, c, n_local=c.e_pad, expert_offset=0,
                         capacity=_capacity(b * s, c, 1))
    else:
        msize = dict(zip(mesh.axis_names, mesh.devices.shape))
        n_model = msize[rules.tensor]
        n_local = c.e_pad // n_model
        n_bsh = _batch_shards(mesh, rules)
        # decode (tokens < batch shards): replicate tokens over data too
        if (b * s) % n_bsh == 0 and (b * s) >= n_bsh:
            batch_ax = batch_axes(mesh, rules) or None
            cap = _capacity(b * s // n_bsh, c, 1)
        else:
            batch_ax = None
            cap = _capacity(b * s, c, 1)
        fs = rules.fsdp_axes(mesh) or None
        fs_ok = fs is not None and c.d_model % rules.fsdp_size(mesh) == 0
        wspec_df = P(rules.tensor, fs if fs_ok else None, None)
        wspec_fd = P(rules.tensor, None, fs if fs_ok else None)

        # §Perf H3b (REFUTED, kept switchable for the record): combining
        # expert outputs with psum_scatter into the sequence-parallel
        # layout halves psum bytes ON PAPER, but GSPMD cannot reshard the
        # scattered {devices=[256,1]} layout through the backward pass
        # ("involuntary full rematerialization") — measured all-gathers
        # EXPLODED 845→3465 GiB/device.  Default stays psum.
        use_psum_scatter = False
        t_loc = (b * s // n_bsh) if batch_ax else (b * s)
        scatter_ok = use_psum_scatter and s > 1 \
            and t_loc % n_model == 0 and t_loc >= n_model

        def body(xl, router, wg, wu, wd):
            # barrier first: keeps XLA's CPU bf16-dot legalization from
            # commuting converts above the per-layer slice and hoisting a
            # full-depth f32 weight stack out of the layer scan
            xl, router, wg, wu, wd = jax.lax.optimization_barrier(
                (xl, router, wg, wu, wd))
            # gather the FSDP dim (D) of the expert weights
            if fs_ok:
                wg = jax.lax.all_gather(wg, fs, axis=1, tiled=True)
                wu = jax.lax.all_gather(wu, fs, axis=1, tiled=True)
                wd = jax.lax.all_gather(wd, fs, axis=2, tiled=True)
            mi = jax.lax.axis_index(rules.tensor)
            out = _moe_local(xl, {"router": router, "we_gate": wg,
                                  "we_up": wu, "we_down": wd}, c,
                             n_local=n_local, expert_offset=mi * n_local,
                             capacity=cap)
            if scatter_ok:
                return jax.lax.psum_scatter(out, rules.tensor,
                                            scatter_dimension=0, tiled=True)
            return jax.lax.psum(out, rules.tensor)

        if scatter_ok:
            tok_axes = tuple(batch_ax) + (rules.tensor,) if batch_ax \
                else (rules.tensor,)
            out_spec = P(tok_axes, None)
        else:
            out_spec = P(batch_ax, None)
        out = jax.shard_map(
            body, mesh=mesh,
            in_specs=(P(batch_ax, None),
                      P(None, None), wspec_df, wspec_df, wspec_fd),
            out_specs=out_spec,
            check_vma=False,
        )(xf, layer["router"], layer["we_gate"], layer["we_up"],
          layer["we_down"])

    if c.n_shared_experts:
        out = out + (jax.nn.silu(xf @ layer["ws_gate"])
                     * (xf @ layer["ws_up"])) @ layer["ws_down"]
    return out.reshape(b, s, d)


def _batch_shards(mesh, rules):
    m = dict(zip(mesh.axis_names, mesh.devices.shape))
    return int(np.prod([m[a] for a in batch_axes(mesh, rules)]))


def _capacity(tokens_local: int, c: TransformerConfig, n_rows: int) -> int:
    cap = int(tokens_local * c.top_k / max(c.n_experts, 1)
              * c.capacity_factor)
    return max(8, min(cap, tokens_local))


def _moe_local(xf, layer, c: TransformerConfig, n_local: int,
               expert_offset, capacity: int):
    """Device-local top-k dispatch → expert matmuls → combine.

    xf: [T, D] local tokens; expert weights [n_local, D, F] etc.
    Tokens routed to experts outside [offset, offset+n_local) are
    handled by other shards (psum combines).
    """
    t, d = xf.shape
    logits = (xf @ layer["router"]).astype(jnp.float32)       # [T, E]
    gates, experts = jax.lax.top_k(jax.nn.softmax(logits, axis=-1), c.top_k)
    gates = gates / jnp.maximum(jnp.sum(gates, -1, keepdims=True), 1e-9)
    # flatten assignments
    flat_e = experts.reshape(-1)                              # [T*k]
    flat_g = gates.reshape(-1).astype(xf.dtype)
    flat_t = jnp.repeat(jnp.arange(t), c.top_k)
    local = (flat_e >= expert_offset) & (flat_e < expert_offset + n_local)
    le = jnp.where(local, flat_e - expert_offset, n_local)    # n_local = drop
    # position of each assignment within its expert (capacity check)
    onehot = jax.nn.one_hot(le, n_local + 1, dtype=jnp.int32)  # [T*k, nl+1]
    pos = jnp.cumsum(onehot, axis=0) * onehot                  # 1-based
    pos_in_e = jnp.sum(pos, axis=-1) - 1                       # [T*k]
    keep = local & (pos_in_e < capacity)
    slot = jnp.where(keep, le * capacity + pos_in_e, n_local * capacity)
    # dispatch: buffer [n_local*capacity (+1 trash), D]
    buf = jnp.zeros((n_local * capacity + 1, d), xf.dtype)
    buf = buf.at[slot].set(xf[flat_t], mode="drop")
    eb = buf[:n_local * capacity].reshape(n_local, capacity, d)
    h = jax.nn.silu(jnp.einsum("ecd,edf->ecf", eb, layer["we_gate"])) \
        * jnp.einsum("ecd,edf->ecf", eb, layer["we_up"])
    eo = jnp.einsum("ecf,efd->ecd", h, layer["we_down"])       # [nl,C,D]
    flat_out = eo.reshape(n_local * capacity, d)
    contrib = jnp.where(keep[:, None], flat_out[jnp.minimum(
        slot, n_local * capacity - 1)], 0.0) * flat_g[:, None]
    out = jnp.zeros((t, d), xf.dtype).at[flat_t].add(contrib)
    return out


# ---------------------------------------------------------------------------
# Full model
# ---------------------------------------------------------------------------

def _layer_windows(c: TransformerConfig, n_layers: int, offset: int):
    """Per-layer attention window (big number = full causal)."""
    FULL = np.int32(2 ** 30)
    idx = np.arange(offset, offset + n_layers)
    if c.sliding_window and c.global_every:
        w = np.where((idx + 1) % c.global_every == 0, FULL,
                     np.int32(c.sliding_window))
    elif c.sliding_window:
        w = np.full(n_layers, np.int32(c.sliding_window))
    else:
        w = np.full(n_layers, FULL)
    return jnp.asarray(w, jnp.int32)


def _scan_layers(x, layers, c, positions, windows, ffn_fn, attn_fn,
                 caches=None, cache_pos=None, mesh=None, rules=None):
    """lax.scan over stacked layer params; optional whole-layer remat."""
    hspec = _hspec(mesh, rules, x.shape[0], x.shape[1]) \
        if mesh is not None else None

    def layer_body(carry, inputs):
        h = carry
        # Barrier the per-layer weight slice: without it XLA hoists
        # bf16→f32 weight converts (a CPU-backend dot legalization) out of
        # the while loop, materializing ALL layers' weights in f32 at once
        # (measured +12 GiB on deepseek decode).  TPU never inserts these
        # converts; the barrier makes the portable lowering match.
        inputs = jax.lax.optimization_barrier(inputs)
        if hspec is not None:
            h = _constrain(h, mesh, hspec)
        if caches is not None:
            layer, window, cache_k, cache_v = inputs
            cache = (cache_k, cache_v)
        else:
            layer, window = inputs
            cache = None
        a, new_cache = attn_fn(rms_norm(h, layer["ln1"], c.norm_eps), layer,
                               c, positions, window, cache, cache_pos,
                               mesh, rules)
        h = h + a
        if hspec is not None:
            h = _constrain(h, mesh, hspec)
        f = ffn_fn(rms_norm(h, layer["ln2"], c.norm_eps), layer)
        h = h + f
        if hspec is not None:
            # exit constraint: the remat-saved carry stack inherits this
            h = _constrain(h, mesh, hspec)
        if caches is not None:
            return h, new_cache
        return h, None

    if c.remat and caches is None:
        policy = None
        if c.remat_policy == "dots":
            policy = jax.checkpoint_policies.dots_with_no_batch_dims_saveable
        body = jax.checkpoint(layer_body, policy=policy)
    else:
        body = layer_body
    xs = (layers, windows) if caches is None \
        else (layers, windows, caches[0], caches[1])
    h, new_caches = jax.lax.scan(body, x, xs)
    return h, new_caches


def forward(params, tokens, c: TransformerConfig, mesh=None, rules=None,
            caches=None, cache_pos=None, positions=None):
    """Token ids [B,S] → final hidden states [B,S,D] (+ updated caches)."""
    x = params["embed"][tokens].astype(c.dtype) * math.sqrt(c.d_model)
    if mesh is not None:
        x = _constrain(x, mesh, _bspec(mesh, rules, x.shape[0], 2))
    if positions is None:
        positions = jnp.broadcast_to(jnp.arange(tokens.shape[1]),
                                     tokens.shape)
    attn = attention_mla if c.mla else attention_dense

    n_moe = (c.n_layers - c.first_dense_layers) if c.moe else 0
    n_dense = c.n_layers - n_moe
    new_caches = {}
    if n_dense:
        wins = _layer_windows(c, n_dense, 0)
        cache_d = caches.get("dense") if caches else None
        x, nc = _scan_layers(
            x, params["dense_layers"], c, positions, wins,
            lambda h, l: ffn_dense(h, l), attn, cache_d, cache_pos,
            mesh, rules)
        new_caches["dense"] = nc
    if n_moe:
        wins = _layer_windows(c, n_moe, n_dense)
        cache_m = caches.get("moe") if caches else None
        x, nc = _scan_layers(
            x, params["moe_layers"], c, positions, wins,
            lambda h, l: moe_block(h, l, c, mesh, rules), attn,
            cache_m, cache_pos, mesh, rules)
        new_caches["moe"] = nc
    x = rms_norm(x, params["final_ln"], c.norm_eps)
    return x, (new_caches if caches is not None else None)


def _unembed(params, c):
    return params["embed"].T if c.tie_embeddings else params["unembed"]


def chunked_softmax_xent(x, labels, unembed, c: TransformerConfig,
                         chunk: int = 512, mesh=None, rules=None):
    """Mean next-token CE without materializing [B,S,V] logits."""
    b, s, d = x.shape
    chunk = min(chunk, s)
    nc = s // chunk
    if mesh is not None:
        lspec = P(_bspec(mesh, rules, b, 0)[0], None,
                  rules.tensor if rules.tensor in mesh.axis_names else None)

    def body(acc, inp):
        xc, yc = inp                                   # [b,chunk,d],[b,chunk]
        logits = (xc @ unembed).astype(jnp.float32)    # [b,chunk,V] TP-sharded
        if mesh is not None:
            logits = _constrain(logits, mesh, lspec)
        lse = jax.nn.logsumexp(logits, axis=-1)
        gold = jnp.take_along_axis(logits, jnp.maximum(yc, 0)[..., None],
                                   axis=-1)[..., 0]
        mask = (yc >= 0).astype(jnp.float32)
        loss = jnp.sum((lse - gold) * mask)
        return (acc[0] + loss, acc[1] + jnp.sum(mask)), None

    xs = (x.reshape(b, nc, chunk, d).transpose(1, 0, 2, 3),
          labels.reshape(b, nc, chunk).transpose(1, 0, 2))
    body = jax.checkpoint(body)
    (tot, cnt), _ = jax.lax.scan(body, (jnp.zeros(()), jnp.zeros(())), xs)
    return tot / jnp.maximum(cnt, 1.0)


def lm_loss(params, batch, c: TransformerConfig, mesh=None, rules=None):
    x, _ = forward(params, batch["tokens"], c, mesh, rules)
    loss = chunked_softmax_xent(x, batch["labels"], _unembed(params, c), c,
                                mesh=mesh, rules=rules)
    if c.mtp:
        # next-next-token prediction: combine h_t with emb(t+1), one proj
        emb_next = params["embed"][batch["tokens"]].astype(c.dtype)
        emb_next = jnp.roll(emb_next, -1, axis=1)
        h2 = jnp.concatenate([rms_norm(x, params["mtp_ln"], c.norm_eps),
                              emb_next], axis=-1) @ params["mtp_proj"]
        labels2 = jnp.roll(batch["labels"], -1, axis=1).at[:, -1].set(-1)
        loss = loss + 0.3 * chunked_softmax_xent(h2, labels2,
                                                 _unembed(params, c), c)
    return loss


def make_train_step(c: TransformerConfig, optimizer, mesh=None, rules=None):
    def train_step(params, opt_state, batch):
        loss, grads = jax.value_and_grad(
            lambda p: lm_loss(p, batch, c, mesh, rules))(params)
        new_params, new_opt = optimizer.update(grads, opt_state, params)
        return new_params, new_opt, {"loss": loss}
    return train_step


# ---------------------------------------------------------------------------
# Serving (prefill + decode with KV caches)
# ---------------------------------------------------------------------------

def cache_shapes(c: TransformerConfig, batch: int, max_len: int):
    """Abstract shapes of the KV caches (stacked per scan group)."""
    n_moe = (c.n_layers - c.first_dense_layers) if c.moe else 0
    n_dense = c.n_layers - n_moe

    def one(n):
        if c.mla:
            return (jax.ShapeDtypeStruct((n, batch, max_len, c.kv_lora_rank),
                                         jnp.bfloat16),
                    jax.ShapeDtypeStruct((n, batch, max_len, c.qk_rope_dim),
                                         jnp.bfloat16))
        return (jax.ShapeDtypeStruct(
                    (n, batch, max_len, c.n_kv_heads, c.d_head), jnp.bfloat16),
                jax.ShapeDtypeStruct(
                    (n, batch, max_len, c.n_kv_heads, c.d_head), jnp.bfloat16))

    out = {}
    if n_dense:
        out["dense"] = one(n_dense)
    if n_moe:
        out["moe"] = one(n_moe)
    return out


def init_caches(c: TransformerConfig, batch: int, max_len: int):
    return jax.tree.map(lambda sd: jnp.zeros(sd.shape, sd.dtype),
                        cache_shapes(c, batch, max_len),
                        is_leaf=lambda x: isinstance(x, jax.ShapeDtypeStruct))


def decode_step(params, caches, token, pos, c: TransformerConfig,
                mesh=None, rules=None):
    """One decode step: token [B,1] + caches → logits [B,V], new caches."""
    positions = jnp.broadcast_to(pos, token.shape)
    x, new_caches = forward(params, token, c, mesh, rules, caches=caches,
                            cache_pos=pos, positions=positions)
    logits = (x[:, -1, :] @ _unembed(params, c)).astype(jnp.float32)
    return logits, new_caches


def prefill(params, tokens, c: TransformerConfig, max_len: int,
            mesh=None, rules=None):
    """Prefill: run tokens through, return last logits + filled caches."""
    caches = init_caches(c, tokens.shape[0], max_len)
    x, new_caches = forward(params, tokens, c, mesh, rules, caches=caches,
                            cache_pos=0)
    logits = (x[:, -1, :] @ _unembed(params, c)).astype(jnp.float32)
    return logits, new_caches
