"""DecoderModel: the full model runtime.

Two execution paths over the same block definitions:

- ``forward`` / ``__call__``: full stack via ``lax.scan`` over the config's
  scan segments (compile-efficient for 94-layer models — HLO size is
  independent of depth). Used by training, the dry-run and full prefill.

- ``run_blocks(start, n)``: partial *vertical* execution of blocks
  [start, start+n) with boundary activations in/out. This is the mechanism
  layered prefill schedules over: group g of an admitted request runs here
  while all other groups only decode. ``start``/``n`` are Python ints
  (static) — the engine jit-caches one executable per group shape, the TPU
  analogue of the paper's CUDA-graph-per-bucket.

Caches mirror the segment structure: ``cache[s][p]`` is a pytree stacked
over that segment's repeats, so both scan (slice per repeat) and engine
(index ``[r]``) paths address the same storage.
"""

from __future__ import annotations

from typing import List, Optional

import jax
import jax.numpy as jnp

from repro.models import attention, blocks, layers, moe
from repro.models.config import FFN_MOE, BlockSpec, ModelConfig
from repro.sharding.partition import shard_hint, shard_seq_hint

Array = jax.Array


def _stack_init(fn, reps: int, key):
    keys = jax.random.split(key, reps)
    return jax.vmap(fn)(keys)


def _stack_zeros(tree, reps: int):
    """Stack a freshly-initialised cache pytree over a segment's repeats,
    preserving non-zero init values (e.g. the xLSTM stabilizer m=-inf)."""
    return jax.tree_util.tree_map(
        lambda x: jnp.broadcast_to(x[None], (reps,) + x.shape), tree)


def _without_experts(bp: dict) -> dict:
    """A MoE block's parameters without its routed experts' weights."""
    return {**bp, "moe": {k: v for k, v in bp["moe"].items()
                          if k not in moe.EXPERT_LEAVES}}


def _with_experts(bp: dict, stacked: dict) -> dict:
    """``bp`` with the routed experts' weights of the layer stack
    ``stacked``, whole: the block reads them in place at its layer."""
    return {**bp, "moe": {**bp["moe"], **{k: stacked["moe"][k]
                                          for k in moe.EXPERT_LEAVES}}}


def sinusoidal_positions(n: int, d: int) -> Array:
    pos = jnp.arange(n, dtype=jnp.float32)[:, None]
    dim = jnp.arange(0, d, 2, dtype=jnp.float32)[None, :]
    ang = pos / jnp.power(10000.0, dim / d)
    pe = jnp.zeros((n, d), jnp.float32)
    pe = pe.at[:, 0::2].set(jnp.sin(ang)).at[:, 1::2].set(jnp.cos(ang))
    return pe


class DecoderModel:
    def __init__(self, cfg: ModelConfig, *, unroll: bool = False,
                 remat: bool = False):
        self.cfg = cfg.validate()
        self.specs = cfg.block_specs()
        self.segments = cfg.scan_segments()
        self.index_map = cfg.block_index_map()
        self.n_blocks = cfg.n_layers
        # unroll=True replaces the segment lax.scan with a python loop:
        # bigger HLO but exact cost_analysis (XLA counts while bodies once)
        # — used by the dry-run for faithful roofline numbers.
        self.unroll = unroll
        # remat=True checkpoints each block in the no-cache (training)
        # forward so the backward pass recomputes activations — required to
        # fit 4k-seq training batches in 16 GB HBM.
        self.remat = remat

    def _experts_in_place(self, spec: BlockSpec, x: Array, gmm_fn,
                          moe_dispatch: str) -> bool:
        """Whether block ``spec`` over x (B, S, D) reads its routed experts
        in place from its segment's layer stacks, by (layer, expert),
        rather than from a per-layer slice: the ragged jnp tile loop's
        operand would be a buffer of its own, one copy of every expert of
        the layer per call (1.21 GB at Qwen3-30B-A3B widths)."""
        return spec.ffn == FFN_MOE and moe.reads_experts_in_place(
            self.cfg, x.shape[0], x.shape[1], gmm_fn, moe_dispatch)

    # -- init ---------------------------------------------------------------

    def init(self, key) -> dict:
        cfg = self.cfg
        k_embed, k_blocks, k_norm, k_enc = jax.random.split(key, 4)
        params: dict = {"embed": layers.init_embed(cfg, k_embed),
                        "final_norm": layers.init_norm(cfg)}
        seg_params = []
        bkeys = jax.random.split(k_blocks, len(self.segments))
        for (pattern, reps), sk in zip(self.segments, bkeys):
            pkeys = jax.random.split(sk, len(pattern))
            seg_params.append({
                "pattern": [
                    _stack_init(lambda k, sp=sp: blocks.init_block(cfg, sp, k),
                                reps, pk)
                    for sp, pk in zip(pattern, pkeys)
                ]
            })
        params["segments"] = seg_params
        if cfg.encoder.enabled:
            enc_spec = BlockSpec(mixer="gqa", ffn="dense")
            ekeys = jax.random.split(k_enc, cfg.encoder.n_layers + 1)
            params["encoder"] = {
                "blocks": [blocks.init_block(cfg, enc_spec, ek)
                           for ek in ekeys[:-1]],
                "final_norm": layers.init_norm(cfg),
            }
        return params

    def init_cache(self, batch: int, max_len: int, dtype=None) -> list:
        cfg = self.cfg
        cache = []
        for pattern, reps in self.segments:
            cache.append([
                _stack_zeros(
                    blocks.init_block_cache(cfg, sp, batch, max_len, dtype), reps)
                for sp in pattern
            ])
        return cache

    # -- encoder (whisper) ----------------------------------------------------

    def encode(self, params, frames: Array) -> Array:
        """frames: (B, T, D) precomputed frontend embeddings (stub)."""
        cfg = self.cfg
        x = frames.astype(cfg.dtype) + sinusoidal_positions(
            frames.shape[1], cfg.d_model).astype(cfg.dtype)
        pos = jnp.broadcast_to(
            jnp.arange(frames.shape[1], dtype=jnp.int32)[None], frames.shape[:2])
        enc_spec = BlockSpec(mixer="gqa", ffn="dense")
        for bp in params["encoder"]["blocks"]:
            # bidirectional self-attention: reuse the block with causal masking
            # disabled by giving every query the max position.
            h = layers.apply_norm(cfg, bp["ln1"], x)
            full_pos = jnp.full_like(pos, frames.shape[1] - 1)
            out, _ = attention.apply_gqa(cfg, enc_spec, bp["attn"], h,
                                         positions=full_pos, cache=None)
            x = x + out
            h2 = layers.apply_norm(cfg, bp["ln2"], x)
            x = x + layers.apply_mlp(cfg, bp["mlp"], h2)
        return layers.apply_norm(cfg, params["encoder"]["final_norm"], x)

    def precompute_cross_kv(self, params, enc_out: Array) -> list:
        """Per-block encoder K/V, in segment layout, to merge into a cache."""
        out = []
        for (pattern, reps), seg in zip(self.segments, params["segments"]):
            pos_list = []
            for p_idx, sp in enumerate(pattern):
                if not sp.cross_attn or not sp.is_attention():
                    pos_list.append(None)
                    continue
                def one(bp):
                    xk, xv = attention.encode_cross_kv(self.cfg, bp["attn"], enc_out)
                    return {"xk": xk, "xv": xv}
                pos_list.append(jax.vmap(one)(seg["pattern"][p_idx]))
            out.append(pos_list)
        return out

    # -- embedding / head ------------------------------------------------------

    def embed(self, params, tokens: Array,
              extra_embeds: Optional[Array] = None,
              positions: Optional[Array] = None) -> Array:
        x = layers.embed_tokens(self.cfg, params["embed"], tokens)
        if extra_embeds is not None:
            # VLM stub: precomputed patch embeddings prepended to the text.
            x = jnp.concatenate([extra_embeds.astype(x.dtype), x], axis=1)
        if self.cfg.pos_emb == "learned" and positions is not None:
            x = x + params["embed"]["pos"][
                jnp.clip(positions, 0, self.cfg.max_seq_len - 1)].astype(x.dtype)
        return shard_hint(x, "batch", None, None)

    def logits(self, params, x: Array) -> Array:
        x = layers.apply_norm(self.cfg, params["final_norm"], x)
        return layers.unembed(self.cfg, params["embed"], x)

    # -- full forward (scan over segments) -------------------------------------

    def run_all(self, params, x: Array, *, positions: Array,
                offset: Optional[Array] = None, cache: Optional[list] = None,
                enc_out: Optional[Array] = None, valid: Optional[Array] = None,
                gmm_fn=None, dropless: bool = False,
                moe_dispatch: str = "dense"):
        cfg = self.cfg
        new_cache: Optional[list] = [] if cache is not None else None
        aux_counts: List[Array] = []
        aux_loss = jnp.zeros((), jnp.float32)
        aux_dropped = jnp.zeros((), jnp.int32)

        for s, (pattern, reps) in enumerate(self.segments):
            seg = params["segments"][s]["pattern"]
            # scanned blocks that read their experts in place: the scan
            # carries the layer index in place of those three leaves
            in_place = [reps > 1 and not self.unroll
                        and self._experts_in_place(sp, x, gmm_fn,
                                                   moe_dispatch)
                        for sp in pattern]

            def body(h, xs):
                ps, cs, layer = xs
                new_cs, auxes = [], []
                for p_idx, sp in enumerate(pattern):
                    bp, ex_layer = ps[p_idx], None
                    if in_place[p_idx]:
                        bp, ex_layer = _with_experts(bp, seg[p_idx]), layer

                    def block_fn(bp, h_, sp=sp, c_=(cs[p_idx] if cs is not None
                                                    else None),
                                 ex_layer=ex_layer):
                        return blocks.apply_block(
                            cfg, sp, bp, h_, positions=positions,
                            offset=offset, cache=c_, enc_out=enc_out,
                            valid=valid, gmm_fn=gmm_fn, dropless=dropless,
                            moe_dispatch=moe_dispatch, expert_layer=ex_layer)
                    if self.remat and cs is None:
                        block_fn = jax.checkpoint(block_fn)
                    h, nc, aux = block_fn(bp, h)
                    h = shard_seq_hint(h)
                    new_cs.append(nc)
                    auxes.append(aux)
                return h, (new_cs if cs is not None else None, auxes)

            cs_stacked = cache[s] if cache is not None else None
            if self.unroll and reps > 1:
                auxes_acc = None
                ncs_acc = [] if cache is not None else None
                for r in range(reps):
                    ps = [jax.tree_util.tree_map(lambda a: a[r], t)
                          for t in seg]
                    cs = ([jax.tree_util.tree_map(lambda a: a[r], t)
                           for t in cs_stacked] if cache is not None else None)
                    x, (ncs, auxes) = body(x, (ps, cs, None))
                    if cache is not None:
                        ncs_acc.append(ncs)
                    if auxes_acc is None:
                        auxes_acc = [[a] for a in auxes]
                    else:
                        for lst, a in zip(auxes_acc, auxes):
                            lst.append(a)
                auxes_stacked = [
                    jax.tree_util.tree_map(lambda *xs: jnp.stack(xs), *lst)
                    for lst in auxes_acc]
                if cache is not None:
                    new_cache.append([
                        jax.tree_util.tree_map(
                            lambda *xs: jnp.stack(xs),
                            *[ncs_acc[r][p_i] for r in range(reps)])
                        for p_i in range(len(pattern))])
            elif reps == 1:
                # no scan needed; avoids degenerate length-1 scans
                ps = [jax.tree_util.tree_map(lambda a: a[0], t) for t in seg]
                cs = ([jax.tree_util.tree_map(lambda a: a[0], t)
                       for t in cs_stacked] if cache is not None else None)
                x, (ncs, auxes) = body(x, (ps, cs, None))
                if cache is not None:
                    new_cache.append([jax.tree_util.tree_map(
                        lambda a: a[None], t) for t in ncs])
                auxes_stacked = [jax.tree_util.tree_map(lambda a: a[None], a_)
                                 for a_ in auxes]
            else:
                seg_xs = [_without_experts(t) if ip else t
                          for t, ip in zip(seg, in_place)]
                layer_ids = jnp.arange(reps) if any(in_place) else None
                x, (ncs, auxes_stacked) = jax.lax.scan(
                    body, x, (seg_xs, cs_stacked, layer_ids))
                if cache is not None:
                    new_cache.append(ncs)
            # collect aux in block order: (reps, P, E) -> (reps*P, E)
            counts = jnp.stack([a["expert_counts"] for a in auxes_stacked],
                               axis=1)
            aux_counts.append(counts.reshape(-1, counts.shape[-1]))
            aux_loss = aux_loss + sum(jnp.sum(a["aux_loss"]) for a in auxes_stacked)
            aux_dropped = aux_dropped + sum(
                jnp.sum(a["dropped"]) for a in auxes_stacked)

        aux = {
            "expert_counts": jnp.concatenate(aux_counts, axis=0),  # (L, E)
            "aux_loss": aux_loss,
            "dropped": aux_dropped,
        }
        return x, new_cache, aux

    def forward(self, params, tokens: Array, *,
                positions: Optional[Array] = None,
                offset: Optional[Array] = None,
                cache: Optional[list] = None,
                enc_out: Optional[Array] = None,
                extra_embeds: Optional[Array] = None,
                valid: Optional[Array] = None,
                gmm_fn=None, dropless: bool = False,
                moe_dispatch: str = "dense"):
        """tokens: (B,S) -> (logits (B,S,V), new_cache, aux)."""
        b, s = tokens.shape
        if offset is None and cache is not None:
            offset = jnp.zeros((b,), jnp.int32)
        if positions is None:
            base = offset if offset is not None else jnp.zeros((b,), jnp.int32)
            positions = base[:, None] + jnp.arange(s, dtype=jnp.int32)[None]
        if extra_embeds is not None:
            s_all = s + extra_embeds.shape[1]
            base = offset if offset is not None else jnp.zeros((b,), jnp.int32)
            positions = base[:, None] + jnp.arange(s_all, dtype=jnp.int32)[None]
        x = self.embed(params, tokens, extra_embeds, positions=positions)
        x, new_cache, aux = self.run_all(params, x, positions=positions,
                                         offset=offset, cache=cache,
                                         enc_out=enc_out, valid=valid,
                                         gmm_fn=gmm_fn, dropless=dropless,
                                         moe_dispatch=moe_dispatch)
        return self.logits(params, x), new_cache, aux

    __call__ = forward

    # -- partial vertical execution (the layered-prefill primitive) -------------

    def run_blocks(self, params, x: Array, start: int, n: int, *,
                   positions: Array, offset: Optional[Array] = None,
                   cache: Optional[list] = None,
                   enc_out: Optional[Array] = None,
                   valid: Optional[Array] = None, gmm_fn=None,
                   dropless: bool = False, moe_dispatch: str = "dense"):
        """Run blocks [start, start+n) over x (B,S,D). start/n are static.
        Returns (x', cache', aux-list-in-block-order).

        B is the caller's batch axis and is fully vectorized: the engine's
        packed layer-group path runs ALL prefill slices sharing this block
        range as one call, with ``cache`` holding a slot-VECTOR of rows
        (leaves ``(reps, B, ...)`` gathered by ``ops.gather_slot_rows``),
        per-row ``offset``/``valid`` masking, and bucket-padded rows that
        are no-ops end to end (their KV writes and recurrent-state updates
        are suppressed by ``valid``)."""
        auxes = []
        if cache is not None:
            # one shallow per-segment copy up front (NOT per block): the
            # caller's list structure is never mutated, and the packed hot
            # path does not rebuild the tree n times per call
            cache = [list(seg) for seg in cache]
        for b in range(start, start + n):
            s, r, p_idx = self.index_map[b]
            spec = self.specs[b]
            # read this block's weights only once the previous block has
            # run, so that XLA places no block's weight slices (a copy
            # where the consumer needs a buffer of its own) ahead of the
            # blocks before it.  Routed experts read in place are not
            # sliced at all: the tile loop indexes the stack at layer r.
            x, seg = jax.lax.optimization_barrier(
                (x, params["segments"][s]["pattern"][p_idx]))
            if self._experts_in_place(spec, x, gmm_fn, moe_dispatch):
                ex_layer = r
                bp = _with_experts(jax.tree_util.tree_map(
                    lambda a: a[r], _without_experts(seg)), seg)
            else:
                ex_layer = None
                bp = jax.tree_util.tree_map(lambda a: a[r], seg)
            c = (jax.tree_util.tree_map(lambda a: a[r], cache[s][p_idx])
                 if cache is not None else None)
            x, nc, aux = blocks.apply_block(
                self.cfg, spec, bp, x, positions=positions, offset=offset,
                cache=c, enc_out=enc_out, valid=valid, gmm_fn=gmm_fn,
                dropless=dropless, moe_dispatch=moe_dispatch,
                expert_layer=ex_layer)
            if cache is not None:
                cache[s][p_idx] = jax.tree_util.tree_map(
                    lambda full, new: full.at[r].set(new.astype(full.dtype)),
                    cache[s][p_idx], nc)
            auxes.append(aux)
        return x, cache, auxes
