"""BERT4Rec (Sun et al., arXiv:1904.06690) — bidirectional transformer
over item sequences, trained with the cloze (masked item) objective.

Encoder-only: there is no autoregressive decode step; all serving shapes
lower full forward passes (DESIGN.md §4).  The paper's unlearning
technique does NOT apply here (learned sequential model — documented in
DESIGN.md §Arch-applicability).
"""
from __future__ import annotations

import dataclasses
import math
from typing import Optional

import jax
import jax.numpy as jnp

import numpy as np
from jax.sharding import PartitionSpec as P

from repro.models.common import layer_norm


@dataclasses.dataclass(frozen=True)
class Bert4RecConfig:
    name: str = "bert4rec"
    n_items: int = 1_000_000        # +2 special tokens (pad=0, mask=1)
    embed_dim: int = 64
    n_blocks: int = 2
    n_heads: int = 2
    seq_len: int = 200
    d_ff: int = 256
    dtype: Optional[object] = jnp.float32

    @property
    def vocab(self):
        # pad to a multiple of 512 so the item table row-shards over any
        # mesh (pad=0, mask=1 special tokens included)
        return (self.n_items + 2 + 511) // 512 * 512

    def n_params(self) -> int:
        d = self.embed_dim
        per_block = 4 * d * d + 2 * d * self.d_ff + 4 * d + self.d_ff + d
        return self.vocab * d + self.seq_len * d \
            + self.n_blocks * per_block + 2 * d


def _block_shapes(c: Bert4RecConfig):
    d, f, L = c.embed_dim, c.d_ff, c.n_blocks
    return {
        "wq": (L, d, d), "wk": (L, d, d), "wv": (L, d, d), "wo": (L, d, d),
        "ln1_w": (L, d), "ln1_b": (L, d), "ln2_w": (L, d), "ln2_b": (L, d),
        "w1": (L, d, f), "b1": (L, f), "w2": (L, f, d), "b2": (L, d),
    }


def param_shapes(c: Bert4RecConfig):
    return {
        "item_emb": (c.vocab, c.embed_dim),
        "pos_emb": (c.seq_len, c.embed_dim),
        "blocks": _block_shapes(c),
        "out_ln_w": (c.embed_dim,), "out_ln_b": (c.embed_dim,),
        "out_bias": (c.vocab,),
    }


def init_params(c: Bert4RecConfig, key):
    shapes = param_shapes(c)
    flat, treedef = jax.tree_util.tree_flatten_with_path(
        shapes, is_leaf=lambda x: isinstance(x, tuple))
    keys = jax.random.split(key, len(flat))
    leaves = []
    for (path, shape), k in zip(flat, keys):
        name = path[-1].key
        if name.endswith(("_b", "bias")):
            leaves.append(jnp.zeros(shape, c.dtype))
        elif name.endswith("_w"):
            leaves.append(jnp.ones(shape, c.dtype))
        else:
            leaves.append((jax.random.normal(k, shape, jnp.float32)
                           * 0.02).astype(c.dtype))
    return jax.tree_util.tree_unflatten(treedef, leaves)


def abstract_params(c: Bert4RecConfig):
    return jax.tree.map(lambda s: jax.ShapeDtypeStruct(s, c.dtype),
                        param_shapes(c), is_leaf=lambda x: isinstance(x, tuple))


def param_pspecs(c: Bert4RecConfig, mesh, rules):
    # item table rows sharded over every mesh axis (model-parallel
    # embeddings): vocab is padded to a multiple of 512 at init.
    all_axes = tuple(mesh.axis_names)
    rows = all_axes if c.vocab % int(np.prod(mesh.devices.shape)) == 0 \
        else (rules.tensor if rules.tensor in mesh.axis_names else None)
    blocks = {k: P(*([None] * len(s)))
              for k, s in _block_shapes(c).items()}
    return {
        "item_emb": P(rows, None), "pos_emb": P(None, None),
        "blocks": blocks,
        "out_ln_w": P(None), "out_ln_b": P(None), "out_bias": P(rows),
    }


def encoder(params, ids, c: Bert4RecConfig, mesh=None, rules=None):
    """ids [B,S] → hidden [B,S,D] (bidirectional, pad-masked)."""
    b, s = ids.shape
    x = params["item_emb"][ids].astype(c.dtype) \
        + params["pos_emb"][None, :s, :].astype(c.dtype)
    from repro.models.dlrm import _constrain_batchwise
    x = _constrain_batchwise(x, mesh, rules, b)
    pad = (ids == 0)
    bias = jnp.where(pad[:, None, None, :], -1e30, 0.0)     # [B,1,1,S]
    h, d = c.n_heads, c.embed_dim // c.n_heads
    scale = 1.0 / math.sqrt(d)

    def body(x, blk):
        q = (x @ blk["wq"]).reshape(b, s, h, d)
        k = (x @ blk["wk"]).reshape(b, s, h, d)
        v = (x @ blk["wv"]).reshape(b, s, h, d)
        scores = jnp.einsum("bqhd,bkhd->bhqk", q, k).astype(jnp.float32)
        probs = jax.nn.softmax(scores * scale + bias, axis=-1).astype(x.dtype)
        att = jnp.einsum("bhqk,bkhd->bqhd", probs, v).reshape(b, s, -1)
        x = layer_norm(x + att @ blk["wo"], blk["ln1_w"], blk["ln1_b"])
        f = jax.nn.gelu(x @ blk["w1"] + blk["b1"]) @ blk["w2"] + blk["b2"]
        x = layer_norm(x + f, blk["ln2_w"], blk["ln2_b"])
        x = _constrain_batchwise(x, mesh, rules, b)
        return x, None

    # remat: [B,h,S,S] attention scores are recomputed in backward rather
    # than saved (B=65536 training cell: −21 GiB peak)
    x, _ = jax.lax.scan(jax.checkpoint(body), x, params["blocks"])
    return layer_norm(x, params["out_ln_w"], params["out_ln_b"])


def forward_logits(params, ids, c: Bert4RecConfig):
    """Full-vocab logits at every position (tied item embeddings)."""
    x = encoder(params, ids, c)
    return (x @ params["item_emb"].T.astype(c.dtype)) + params["out_bias"]


def cloze_loss(params, batch, c: Bert4RecConfig):
    """batch: {"ids": [B,S] (with [MASK]=1 tokens), "targets": [B,S]
    (true item at masked positions, -1 elsewhere)}."""
    x = encoder(params, batch["ids"], c)
    logits = (x @ params["item_emb"].T.astype(c.dtype)
              + params["out_bias"]).astype(jnp.float32)
    t = batch["targets"]
    mask = (t >= 0).astype(jnp.float32)
    lse = jax.nn.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, jnp.maximum(t, 0)[..., None],
                               axis=-1)[..., 0]
    return jnp.sum((lse - gold) * mask) / jnp.maximum(jnp.sum(mask), 1.0)


def sampled_cloze_loss(params, batch, c: Bert4RecConfig, mesh=None,
                       rules=None):
    """Cloze loss with sampled negatives — the big-vocab (10⁶ items)
    training path: full [B,S,V] logits are never materialized.

    batch: {"ids": [B,S], "mask_pos": [B,M], "targets": [B,M] (−1 pad),
            "negatives": [K]}  — targets scored against K shared sampled
    negatives + the gold item (standard sampled softmax).
    """
    x = encoder(params, batch["ids"], c, mesh, rules)       # [B,S,D]
    mp = jnp.maximum(batch["mask_pos"], 0)
    h = jnp.take_along_axis(x, mp[..., None], axis=1)       # [B,M,D]
    t = batch["targets"]
    emb = params["item_emb"]
    gold_e = emb[jnp.maximum(t, 0)].astype(c.dtype)         # [B,M,D]
    neg_e = emb[batch["negatives"]].astype(c.dtype)         # [K,D]
    gold_logit = jnp.sum(h * gold_e, -1).astype(jnp.float32)
    neg_logits = jnp.einsum("bmd,kd->bmk", h, neg_e).astype(jnp.float32)
    lse = jax.nn.logsumexp(
        jnp.concatenate([gold_logit[..., None], neg_logits], -1), axis=-1)
    mask = (t >= 0).astype(jnp.float32)
    return jnp.sum((lse - gold_logit) * mask) / jnp.maximum(jnp.sum(mask),
                                                            1.0)


def make_train_step(c: Bert4RecConfig, optimizer, sampled: bool = False,
                    mesh=None, rules=None):
    def train_step(params, opt_state, batch):
        if sampled:
            fn = lambda p: sampled_cloze_loss(p, batch, c, mesh, rules)
        else:
            fn = lambda p: cloze_loss(p, batch, c)
        l, grads = jax.value_and_grad(fn)(params)
        params, opt_state = optimizer.update(grads, opt_state, params)
        return params, opt_state, {"loss": l}
    return train_step


def serve_step(params, batch, c: Bert4RecConfig, top_n: int = 20,
               mesh=None, rules=None, vocab_chunk: int = 65536,
               batch_chunk: int = 16384):
    """Next-item recommendation: top-n over the full 10⁶-item catalogue.

    The [B, V] logit matrix is never materialized (262144 × 10⁶ × 4B =
    1 TB): we scan the item table in vocab chunks keeping a running
    top-n — the same streaming-top-k schedule as kernels.knn_topk.
    Huge serve batches additionally run in batch chunks (bulk scoring).
    """
    if batch["ids"].shape[0] > batch_chunk:
        from repro.models.common import map_batch_chunks
        return map_batch_chunks(
            lambda sub: serve_step(params, sub, c, top_n, mesh, rules,
                                   vocab_chunk, batch_chunk),
            batch, batch_chunk, keys=["ids"])
    x = encoder(params, batch["ids"], c, mesh, rules)
    q = x[:, -1, :]                                       # [B, D]
    v = params["item_emb"].shape[0]

    # §Perf H2 (see EXPERIMENTS.md): GSPMD turns a top-k over the sharded
    # catalogue into full-score all-gathers (~1 TiB/device measured), and
    # constraints alone only move the gather.  The fix is a MANUAL
    # shard_map: catalogue rows over 'model' (one small reshard), each
    # device scores its V/TP rows and keeps a LOCAL top-n; only
    # [B, TP·top_n] candidates cross the wire.
    if mesh is not None and rules is not None \
            and rules.tensor in mesh.axis_names \
            and v % int(dict(zip(mesh.axis_names,
                                 mesh.devices.shape))[rules.tensor]) == 0:
        from jax.sharding import PartitionSpec as P
        from repro.parallel.sharding import batch_axes
        import numpy as np
        sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
        tp_ax = rules.tensor
        n_tp = sizes[tp_ax]
        b_ax = batch_axes(mesh, rules) or None
        nb = int(np.prod([sizes[a] for a in (b_ax or ())])) or 1
        if q.shape[0] % nb:
            b_ax = None
        v_loc = v // n_tp

        def body(ql, e_loc, b_loc):
            mi = jax.lax.axis_index(tp_ax)
            scores = (ql @ e_loc.T.astype(c.dtype)
                      + b_loc).astype(jnp.float32)       # [B_loc, V_loc]
            lv, li = jax.lax.top_k(scores, top_n)        # local top-n
            li = li + mi * v_loc
            cv = jax.lax.all_gather(lv, tp_ax, axis=1, tiled=True)
            ci = jax.lax.all_gather(li, tp_ax, axis=1, tiled=True)
            tv, tp_ = jax.lax.top_k(cv, top_n)
            return tv, jnp.take_along_axis(ci, tp_, axis=1)

        return jax.shard_map(
            body, mesh=mesh,
            in_specs=(P(b_ax, None), P(tp_ax, None), P(tp_ax)),
            out_specs=(P(b_ax, None), P(b_ax, None)),
            check_vma=False,
        )(q, params["item_emb"], params["out_bias"])

    # single-device / unshardable fallback: vocab-chunked streaming top-k
    chunk = min(vocab_chunk, v)
    nc = v // chunk
    emb = params["item_emb"][:nc * chunk].reshape(nc, chunk, c.embed_dim)
    bias = params["out_bias"][:nc * chunk].reshape(nc, chunk)

    def chunk_body(carry, inp):
        vals, idx = carry
        e, b_, ci = inp
        scores = (q @ e.T.astype(c.dtype) + b_).astype(jnp.float32)
        tile_idx = ci * chunk + jnp.arange(chunk)[None, :]
        m_vals = jnp.concatenate([vals, scores], axis=1)
        m_idx = jnp.concatenate(
            [idx, jnp.broadcast_to(tile_idx, scores.shape)], axis=1)
        tv, tp = jax.lax.top_k(m_vals, top_n)
        return (tv, jnp.take_along_axis(m_idx, tp, axis=1)), None

    init = (jnp.full((q.shape[0], top_n), -jnp.inf, jnp.float32),
            jnp.zeros((q.shape[0], top_n), jnp.int32))
    (vals, idx), _ = jax.lax.scan(chunk_body, init,
                                  (emb, bias, jnp.arange(nc)))
    return vals, idx


def retrieval_step(params, batch, c: Bert4RecConfig, top_n: int = 100,
                   mesh=None, rules=None):
    """retrieval_cand cell: one query's last hidden state scored against
    ``candidates`` item-embedding rows (uses the kNN kernel shape)."""
    x = encoder(params, batch["ids"], c, mesh, rules)   # [1,S,D]
    q = x[:, -1, :]                                 # [1,D]
    scores = q @ batch["candidates"].T.astype(c.dtype)
    return jax.lax.top_k(scores.astype(jnp.float32), top_n)
