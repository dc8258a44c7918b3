"""The KV cache behind ONE object, in four implementations and one
composition.

What the forwards of models/llama.py, the decode kernel call and the
engine's sizing need of a cache is asked of the object that
``kv_cache_of(cfg)`` returns, and of nothing else:

- build it: the paged pool (``init_pool``) and the dense
  absolute-position cache (``init_dense``);
- size it: ``token_bytes`` a cached token a layer, ``page_size``;
- write it: rows or whole pages (``write``), a dense cache's rows
  (``put_dense``);
- read it, which is attention: a slot's window gathered (``attend_window``:
  the verify forward, and the decode step off the chip), a chunk's prefix
  streamed block by block (``attend_prefix``), the tokens given alone or
  the dense cache's rows (``attend_tokens``), and the Pallas decode kernel with the pool in the layer scan's carry
  (``kernel_attend``);
- shard it: ``pool_spec``.

Every ``attend_*`` takes a layer's ``(q, k, v)`` as ``decoder_layer``
hands them and returns the attention output in VALUE space,
(B, S, H, v_head_dim), and what the layer leaves in the cache is ``(k,
v)`` as handed:

``HeadKV``  per-head keys and values. Pool ``{"k", "v"}: (L, N, KV, page,
            hd)`` (+ ``"ks"/"vs"`` scale planes when int8); ``(k, v)`` are
            (B, S, KV, hd).
``LatentKV`` one latent row a token for all heads (``cfg.kv_lora_rank``).
            ``(k, v)`` are the normed latent ``c`` (B, S, R) and the
            rotated shared key part ``k_r`` (B, S, rope). Pool in TWO
            leaves, ``"c": (L, N, 1, page, R)`` and ``"r": (L, N, 1,
            rope, page)`` — the rotary part TRANSPOSED, positions on the
            lanes: a 576-wide row is not a lane multiple (the chip pads
            it to 640 in HBM and in VMEM), a 64-wide one is padded to
            128, and (rope, page) is whole tiles AND the right-hand side
            of the scores' matmul as it lies. A step reads each cached
            row once and uses ``c`` as key and value: decode and verify
            run ABSORBED (the query carried into the latent space through
            ``wk_b``, ``wv_b`` applied after the sum); a chunk program
            runs EXPANDED, its own tokens and each prefix block it reads
            back taken through ``wk_b`` / ``wv_b`` (a 512-token chunk
            does half the operations that way: one expansion a block
            serves all its queries), so nothing of size prefix x heads x
            head width exists at once.
``SparseLatentKV`` ``LatentKV`` under learned sparse attention
            (``cfg.index_topk``): a THIRD leaf, ``"i": (Lf, N, 1, page,
            index_head_dim)``, the indexer's key a token on the Lf FULL
            layers only (``cfg.layer_index``) — one block table and one
            page id for all three, bytes a token that differ by layer
            (``model_token_bytes``). What a layer leaves in the cache is
            ``(c, k_r, k_i)``, the last zeros on a shared layer and never
            written. A full layer scores the row's cached index keys
            read through its block table and keeps the ``index_topk``
            best (ops/sparse_index.py); the KEEP MASK over the keys is
            returned beside the attention and handed to the layers above
            (models/llama.py ``_run_stack`` carries it); every
            ``attend_*`` takes the layer's ``index`` (projections, whether
            it is full, the mask carried) and reads MASKED: all of the
            row's cached rows, the unchosen keys out of the softmax.
            The decode step runs the latent decode kernel with the mask
            as one more operand (``kernel_attend``: each slot's live
            pages streamed as ``LatentKV``'s are, the pool and the mask
            in the layer scan's carry; only the full layers' windows of
            index keys are gathered, to be scored); the verify forward,
            and the decode step off the chip, gather the window and run
            absorbed (``attend_window``); a chunk runs
            ``LatentKV.attend_prefix`` with the mask as one more operand
            of the chunk kernel.
``RecurrentKV`` the cache of a model whose layers are not all attention
            (``cfg.full_attention_interval``), by COMPOSITION: a paged
            part — ``HeadKV``'s ``{"k", "v"}``, or ``LatentKV``'s ``{"c",
            "r"}`` where the attention layers are latent
            (``cfg.kv_lora_rank``), each what it is alone — over the Lf
            ATTENTION layers only, and two leaves that are not pages, one
            entry a SLOT whatever the sequence's length: ``"s": (Lg,
            slots, Hv, dk, dv)``, the Lg recurrent layers' delta-rule
            state (float32, always), and ``"conv": (Lg, slots,
            (K - 1) * channels)``, the last K - 1 inputs of their
            convolution, a slot's as ONE row: as (K - 1, channels) the
            chip pads 3 rows to 4 and its compiler asks for another
            layout than the buffers have (a program loaded from the
            compile cache then refuses them: PERF.md section 6). The
            forwards hand the attention layers the paged part's readers
            with the layer's place among the attention layers, and the
            recurrent layers ``recur``: the rows' state read by slot at
            the layer's place among ITS kind (zeros where the row's
            sequence starts) and written back there. A state does not
            forgive what rows past a length forgive: tokens past a
            chunk's valid length, idle rows of a decode round and a
            finished row's surplus steps are masked out of it
            (models/llama.py ``_gdn_mixer``, ``_kda_mixer``). ``slots`` is a keyword of
            every forward and of ``init_pool``; its default is one
            sequence a row, row ``b`` in slot ``b``.
"""

from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp

from ..ops.attention import gqa_attention
from ..ops.quant import matmul as qmm
from .configs import LlamaConfig

KVCache = dict[str, jax.Array]


@functools.lru_cache(maxsize=None)
def kv_cache_of(cfg: LlamaConfig):
    """The configuration's cache object: what it says of its attention
    decides, and whether every layer attends; nothing else."""
    if cfg.index_topk:
        return SparseLatentKV(cfg)
    if cfg.recurrent:
        return RecurrentKV(cfg)
    return LatentKV(cfg) if cfg.kv_lora_rank else HeadKV(cfg)


class HeadKV:
    """Per-head K and V: ``{"k","v"}: (L, n_pages, KV, page, hd)``.

    **Heads of 64 values, packed two a lane row** (``pack_heads``, where
    the pool is on ONE chip: ``RecurrentKV``, which refuses a mesh). A
    pool whose last axis is 64 is half a vector register's lanes: the
    decode kernel does not take it and the chip stores it padded to
    twice its bytes. Under lane-width pages such a pool is built as (L,
    n_pages, KV / 2, page, 128) — kv heads ``2j`` and ``2j + 1`` side by
    side in row ``j`` — which is a pure reshape of a token's (KV, hd)
    rows, so every gathered read and every write below is as it was. The
    kernel then sees KV / 2 heads of 128: a query goes in beside zeros
    in the other head's half (its scores are its own head's, exactly),
    and of its 128 output values its own half is kept."""

    leaves = ("k", "v")

    def __init__(self, cfg: LlamaConfig, layers: Optional[int] = None,
                 pack_heads: bool = False):
        """``layers``: the layers that keep rows (all of them; the
        attention layers where ``RecurrentKV`` holds this as its paged
        part)."""
        self.cfg = cfg
        self.n_layers = cfg.num_layers if layers is None else layers
        self.pack_heads = pack_heads

    def _pack(self, page: int) -> int:
        """KV heads a stored row holds (class docstring): 2 or 1."""
        cfg = self.cfg
        return 2 if (self.pack_heads and cfg.head_dim == 64
                     and cfg.num_kv_heads % 2 == 0 and page % 128 == 0) \
            else 1

    # ---------------------------------------------------------------- build

    def init_dense(self, batch: int, max_len: int,
                   dtype: jnp.dtype = jnp.bfloat16) -> KVCache:
        cfg = self.cfg
        shape = (self.n_layers, batch, max_len, cfg.num_kv_heads,
                 cfg.head_dim)
        return {"k": jnp.zeros(shape, dtype), "v": jnp.zeros(shape, dtype)}

    def init_pool(self, n_pages: int, page_size: int,
                  dtype: jnp.dtype = jnp.bfloat16,
                  quantized: bool = False) -> KVCache:
        cfg = self.cfg
        pack = self._pack(page_size)
        if pack > 1 and quantized:
            raise NotImplementedError(
                "an int8 KV pool of packed heads: a row's scale would be "
                "two heads'")
        shape = (self.n_layers, n_pages, cfg.num_kv_heads // pack,
                 page_size, cfg.head_dim * pack)
        if not quantized:
            return {"k": jnp.zeros(shape, dtype),
                    "v": jnp.zeros(shape, dtype)}
        from ..ops.kv_quant import SCALE_DTYPE
        return {"k": jnp.zeros(shape, jnp.int8),
                "v": jnp.zeros(shape, jnp.int8),
                "ks": jnp.zeros(shape[:4], SCALE_DTYPE),
                "vs": jnp.zeros(shape[:4], SCALE_DTYPE)}

    # ----------------------------------------------------------------- size

    def token_bytes(self, itemsize: int, quantized: bool = False) -> int:
        """Bytes a cached token a layer: K and V rows (int8 rows and one
        bf16 scale each when ``quantized``: ops/kv_quant.py)."""
        cfg = self.cfg
        if quantized:
            return cfg.num_kv_heads * 2 * (cfg.head_dim + 2)
        return cfg.num_kv_heads * cfg.head_dim * 2 * itemsize

    def model_token_bytes(self, itemsize: int, quantized: bool = False
                          ) -> int:
        """Bytes a cached token, all the layers that keep rows."""
        return self.n_layers * self.token_bytes(itemsize, quantized)

    @staticmethod
    def page_size(kv_cache: KVCache) -> int:
        return kv_cache["k"].shape[3]

    @property
    def scale(self) -> Optional[float]:
        """What the scores are multiplied by where the configuration
        states it (``attention_multiplier``: ``cfg.score_scale``); None
        leaves every reader its own head_dim ** -0.5."""
        cfg = self.cfg
        return cfg.score_scale if cfg.attention_multiplier else None

    @staticmethod
    def quantized(kv_cache: KVCache) -> bool:
        """Whether a paged pool carries int8 rows + scale leaves."""
        return "ks" in kv_cache

    def kernel_supported(self, page: int) -> bool:
        from ..ops.paged_attention import kernel_supported
        cfg = self.cfg
        pack = self._pack(page)
        return kernel_supported(page, cfg.num_heads,
                                cfg.num_kv_heads // pack,
                                cfg.head_dim * pack)

    def pool_spec(self, mesh, quantized: bool = False) -> dict:
        from ..parallel.sharding import paged_kv_cache_spec
        return paged_kv_cache_spec(self.cfg, mesh, quantized)

    # ---------------------------------------------------------------- write

    def write(self, kv_cache: KVCache, new_k: jax.Array, new_v: jax.Array,
              pages: jax.Array, offsets: Optional[jax.Array] = None
              ) -> KVCache:
        """The one place that writes rows into the paged pool and knows its
        format: the layers' new K and V, stacked (L, ...) as the scan gave
        them, are quantised when the pool has scale planes and written in
        ONE scatter a leaf, after the scan. Two destinations:

        - rows (``offsets`` given): new (L, B, S, KV, hd) to ``pages`` /
          ``offsets`` (B, S), each token's physical page and row in it (a
          decode step is S = 1): one (layer, flat row) index per (slot,
          token, kv-head) over (N, KV, page) flattened. Indexed ``[:,
          row]`` instead, the TPU compiler relayouts the WHOLE pool to
          scatter and back (compile, PR 30).
        - whole pages (``offsets`` None): a chunk's new (L, C, KV, hd), C
          a page multiple, to its C / page physical ``pages``.
        """
        L, N, KV, page, W = kv_cache["k"].shape
        # a token's (KV, hd) rows as the pool keeps them (packed heads:
        # two side by side, a reshape)
        new = {"k": new_k.reshape(new_k.shape[:-2] + (KV, W)),
               "v": new_v.reshape(new_v.shape[:-2] + (KV, W))}
        if self.quantized(kv_cache):
            from ..ops.kv_quant import quantize_rows
            new["k"], new["ks"] = quantize_rows(new_k)    # scales: (..., KV)
            new["v"], new["vs"] = quantize_rows(new_v)
        if offsets is None:
            def put(pool, rows):
                blocks = rows.reshape((L, -1, page) + rows.shape[2:])
                return pool.at[:, pages].set(
                    blocks.swapaxes(2, 3).astype(pool.dtype))
        else:
            flat_idx = ((pages[..., None] * KV + jnp.arange(KV)) * page
                        + offsets[..., None])                   # (B, S, KV)
            layer = jnp.arange(L)[:, None, None, None]

            def put(pool, rows):
                flat = pool.reshape((L, N * KV * page) + pool.shape[4:])
                return flat.at[layer, flat_idx[None]].set(
                    rows.astype(pool.dtype)).reshape(pool.shape)

        with jax.named_scope("attn"):      # the KV write
            return {name: put(kv_cache[name], rows)
                    for name, rows in new.items()}

    def insert_pages(self, kv_cache: KVCache, k_new: jax.Array,
                     v_new: jax.Array, dest: jax.Array) -> KVCache:
        """A prefilled bucket's dense rows (L, 1, S, KV, hd), S a page
        multiple, into the S / page physical pages ``dest`` (the engine's
        admission; bucket overhang past the slot's extent goes to the
        trash page)."""
        L, _, S = k_new.shape[:3]
        KV, page, W = kv_cache["k"].shape[2:]
        nb = S // page
        # (L,1,S,KV,hd) -> (L, nb, KV, page, hd): pool layout keeps KV
        # ahead of page (see llama.init_paged_kv_cache).
        kp = k_new.reshape(L, nb, page, KV, W).swapaxes(2, 3)
        vp = v_new.reshape(L, nb, page, KV, W).swapaxes(2, 3)
        cache = kv_cache
        if self.quantized(kv_cache):
            from ..ops.kv_quant import quantize_rows
            kq, ks = quantize_rows(kp)   # scales: (L, nb, KV, page)
            vq, vs = quantize_rows(vp)
            return {
                "k": cache["k"].at[:, dest].set(kq),
                "v": cache["v"].at[:, dest].set(vq),
                "ks": cache["ks"].at[:, dest].set(
                    ks.astype(cache["ks"].dtype)),
                "vs": cache["vs"].at[:, dest].set(
                    vs.astype(cache["vs"].dtype)),
            }
        return {
            "k": cache["k"].at[:, dest].set(
                kp.astype(cache["k"].dtype)),
            "v": cache["v"].at[:, dest].set(
                vp.astype(cache["v"].dtype)),
        }

    # ----------------------------------------------------------------- read

    def window(self, kv_cache: KVCache, name: str, layer, block_table,
               dtype):
        """One layer's slot windows of leaf ``name`` ("k" or "v") gathered
        from the WHOLE paged pool, (L, N, KV, page, hd) -> (B, P*page, KV,
        hd), by (layer, page) in one step over the pool's flattened
        leading axes (a ``pool[layer]`` first is a copy of the layer's
        whole slab); int8 pages are dequantized via their per-row
        scales."""
        cfg = self.cfg
        pool = kv_cache[name]
        B, P = block_table.shape
        pages = block_table + layer * pool.shape[1]
        g = pool.reshape((-1,) + pool.shape[2:])[pages]  # (B,P,KV,page,hd)
        if self.quantized(kv_cache):
            from ..ops.kv_quant import dequantize_rows
            scales = kv_cache[name + "s"]                   # (L, N, KV, page)
            g = dequantize_rows(
                g, scales.reshape((-1,) + scales.shape[2:])[pages], dtype)
        return g.swapaxes(2, 3).reshape(B, P * pool.shape[3],
                                        cfg.num_kv_heads, cfg.head_dim)

    def attend_tokens(self, q, k, v, lp, positions, kv_valid_len):
        """Attention over the keys given by absolute position: the
        call's own tokens, or a dense cache's rows."""
        return gqa_attention(q, k, v, positions, kv_valid_len,
                             window=lp.get("window"), scale=self.scale)

    def put_dense(self, lp, k, v, row_start):
        """A layer's slices of the dense cache (``lp["cache_k"]`` /
        ``lp["cache_v"]``: (B, T, KV, hd)) with this call's rows written
        at their absolute positions (rows contiguous)."""
        put = jax.vmap(
            lambda c, u, s: jax.lax.dynamic_update_slice(c, u, (s, 0, 0)))
        return put(lp["cache_k"], k, row_start), \
            put(lp["cache_v"], v, row_start)

    def attend_window(self, q, k, v, lp, kv_cache, layer, block_table,
                      rows, positions, kv_valid_len):
        """The verify forward's attention: the slot's window gathered,
        the S current tokens joined in-register."""
        def window(name, new):
            g = self.window(kv_cache, name, layer, block_table, q.dtype)
            # All S current tokens join the window in-register at their
            # logical positions (their pool writes happen in the
            # post-scan scatter); positions past the window drop on
            # scatter — they can only belong to masked garbage rows.
            return g.at[rows, positions].set(new.astype(g.dtype))

        return gqa_attention(q, window("k", k), window("v", v), positions,
                             kv_valid_len, window=lp.get("window"),
                             scale=self.scale)

    def attend_prefix(self, q, k, v, lp, kv_cache, block_table, start,
                      kv_valid_len, layer):
        """A chunk's attention: its prefix streamed from the pool block by
        block, its own tokens in-register. B = 1."""
        return _paged_prefix_attention(
            q, k, v, kv_cache["k"], kv_cache["v"], kv_cache.get("ks"),
            kv_cache.get("vs"), block_table, start, kv_valid_len,
            self.page_size(kv_cache), self.cfg, window=lp.get("window"),
            layer=layer, scale=self.scale)

    def kernel_attend(self, kv_cache: KVCache, block_table, pos_in_win,
                      write_page, write_offset, mesh, act_dtype):
        """The decode step's ``attend`` over the Pallas kernel: attention
        read and row append inside the kernel, the pool in the layer
        scan's carry."""
        from ..ops.paged_attention import paged_attention_decode
        cfg = self.cfg
        # int8-KV pools: the kernel quantizes the appended row itself, so
        # the current token's K/V pass in compute dtype, not pool dtype.
        dt = act_dtype if self.quantized(kv_cache) else kv_cache["k"].dtype
        interp = jax.default_backend() != "tpu"
        KV, hd = kv_cache["k"].shape[2], cfg.head_dim
        pack = cfg.num_kv_heads // KV
        # the kernel's default scale is its own head width's
        scale = self.scale if pack == 1 else cfg.score_scale
        # which part of a packed row a query head's kv head lies in
        part = pack > 1 and (
            jnp.arange(cfg.num_heads)
            // (cfg.num_heads // cfg.num_kv_heads) % pack)[:, None]

        # ``win``: the layer's window as one more (1,) operand, only in a
        # model that has window layers
        def call_kernel(q, pool, ck, cv, li, tbl, lens, wp, off, *win):
            attn, *leaves = paged_attention_decode(
                q, pool["k"], pool["v"], tbl, lens, ck, cv, wp, off, li,
                pool_ks=pool.get("ks"), pool_vs=pool.get("vs"),
                interpret=interp, window=win[0] if win else None,
                scale=scale)
            return attn, dict(zip(("k", "v", "ks", "vs"), leaves))

        if mesh is not None and "tp" in mesh.shape:
            # Pallas has no SPMD partitioning rule, so under a tp mesh the
            # call is shard_mapped: each device runs the kernel on its own
            # H/tp query heads and KV/tp pool shard — table/positions are
            # replicated, and the append lands in the local shard. This is
            # what keeps the v5e-8 TP serving config off the ~10x-slower
            # gather path (VERDICT r3 weak #3).
            from jax.sharding import PartitionSpec as P
            heads = P(None, "tp", None)                     # q, ck, cv, attn
            pool_specs = {
                name: P(None, None, "tp", *(None,) * (leaf.ndim - 3))
                for name, leaf in kv_cache.items()}
            call_kernel = jax.shard_map(
                call_kernel, mesh=mesh,
                in_specs=(heads, pool_specs, heads, heads)
                + (P(),) * (5 + any(cfg.layer_windows)),
                out_specs=(heads, pool_specs), check_vma=False)

        def attend(q, k, v, lp, li, pool):
            win = (lp["window"][None],) if "window" in lp else ()
            q, k, v = q[:, 0], k[:, 0].astype(dt), v[:, 0].astype(dt)
            if pack > 1:    # packed heads (class docstring)
                q = jnp.concatenate([jnp.where(part == r, q, 0)
                                     for r in range(pack)], axis=-1)
                k, v = (a.reshape(a.shape[0], KV, pack * hd)
                        for a in (k, v))
            attn, pool = call_kernel(
                q, pool, k, v, li, block_table, pos_in_win, write_page,
                write_offset, *win)
            if pack > 1:
                attn = sum(jnp.where(part == r,
                                     attn[..., r * hd:(r + 1) * hd], 0)
                           for r in range(pack))
            return attn[:, None], pool

        return attend


class RecurrentKV:
    """A paged part over the attention layers, a state a slot for the
    recurrent ones (module docstring). What is asked of the cache and is
    not a state's — a token's bytes, the page size, whether a kernel
    takes the geometry, the attention over given tokens, a dense cache's
    rows — is the paged part's, as it answers alone."""

    def __init__(self, cfg: LlamaConfig):
        self.cfg = cfg
        self.n_full = sum(cfg.layer_full)
        self.n_recurrent = cfg.num_layers - self.n_full
        # (heads of 64 packed two a lane row: this cache is one chip's)
        self.paged = LatentKV(cfg, layers=self.n_full) if cfg.kv_lora_rank \
            else HeadKV(cfg, layers=self.n_full, pack_heads=True)
        self.leaves = self.paged.leaves + ("s", "conv")
        self.scope = cfg.recurrent_scope + "_state"

    def __getattr__(self, name):
        # only what this class does not define: the paged part's
        if name == "paged":
            raise AttributeError(name)
        return getattr(self.paged, name)

    def _paged(self, kv_cache: KVCache) -> KVCache:
        return {n: kv_cache[n] for n in self.paged.leaves}

    def _state_shapes(self, slots: int) -> tuple[tuple, tuple]:
        cfg = self.cfg
        # a head's state: key width x value width; a state-space layer's
        # (P, N) the other way round, its 128-wide N on the lanes
        # (ops/ssd.py says why)
        head = (cfg.linear_key_head_dim, cfg.linear_value_head_dim)
        if cfg.linear_decay == "ssd":
            head = head[::-1]
        return ((self.n_recurrent, slots, cfg.linear_num_value_heads) + head,
                (self.n_recurrent, slots,
                 (cfg.linear_conv_kernel_dim - 1) * cfg.linear_channels))

    def _state(self, slots: int, dtype) -> KVCache:
        s, conv = self._state_shapes(slots)
        return {"s": jnp.zeros(s, jnp.float32),
                "conv": jnp.zeros(conv, dtype)}

    @staticmethod
    def _as_stored(name: str, rows: jax.Array, leaf: jax.Array) -> jax.Array:
        """Rows of a state leaf in its dtype, a tail's (K - 1, channels)
        as the one row the leaf keeps."""
        if name == "conv":
            rows = rows.reshape(rows.shape[:-2] + (-1,))
        return rows.astype(leaf.dtype)

    # ---------------------------------------------------------------- build

    def init_dense(self, batch: int, max_len: int,
                   dtype: jnp.dtype = jnp.bfloat16) -> KVCache:
        state = self._state(batch, dtype)
        # a dense cache's layers ride the scan whole: its tail stays
        # (K - 1, channels) a row, as the mixer takes and leaves it
        state["conv"] = state["conv"].reshape(
            state["conv"].shape[:2] + (-1, self.cfg.linear_channels))
        return {**self.paged.init_dense(batch, max_len, dtype), **state}

    def init_pool(self, n_pages: int, page_size: int,
                  dtype: jnp.dtype = jnp.bfloat16,
                  quantized: bool = False, slots: int = 1) -> KVCache:
        if quantized:
            raise NotImplementedError(
                "an int8 KV pool beside a recurrent state is not supported")
        return {**self.paged.init_pool(n_pages, page_size, dtype),
                **self._state(slots, dtype)}

    # ----------------------------------------------------------------- size

    def slot_bytes(self, itemsize: int) -> int:
        """Bytes a sequence costs whatever its length: the recurrent
        layers' state and convolution tail."""
        s, conv = self._state_shapes(1)
        return math.prod(s) * 4 + math.prod(conv) * itemsize

    def pool_spec(self, mesh, quantized: bool = False) -> dict:
        raise NotImplementedError(
            "a recurrent state has no sharding over a mesh")

    # ------------------------------------------------------- attention layers

    def attend_window(self, q, k, v, lp, kv_cache, layer, *args):
        return self.paged.attend_window(q, k, v, lp, kv_cache,
                                        self.cfg.full_before(layer), *args)

    def attend_prefix(self, q, k, v, lp, kv_cache, block_table, start,
                      kv_valid_len, layer, **kernel):
        return self.paged.attend_prefix(q, k, v, lp, kv_cache, block_table,
                                        start, kv_valid_len,
                                        self.cfg.full_before(layer),
                                        **kernel)

    def kernel_attend(self, kv_cache: KVCache, *args):
        inner = self.paged.kernel_attend(kv_cache, *args)

        def attend(q, k, v, lp, li, pool):
            attn, new = inner(q, k, v, lp, self.cfg.full_before(li), pool)
            return attn, {**pool, **new}

        return attend

    # ------------------------------------------------------ recurrent layers

    @staticmethod
    def from_zeros(rows: jax.Array, fresh: jax.Array) -> jax.Array:
        """The rows' state (or tail) with zeros where a row's sequence
        STARTS (``fresh`` (B,) bool), whatever was there."""
        return jnp.where(fresh.reshape((-1,) + (1,) * (rows.ndim - 1)),
                         jnp.zeros((), rows.dtype), rows)

    def recur(self, kv_cache: Optional[KVCache], slots, fresh,
              carried: bool = False, live=None):
        """``(load, store, step)`` for a forward's recurrent layers:
        ``load(lg, state, lp)`` gives the rows' ``(S, conv tail)``
        entering recurrent layer ``lg`` — read from the pool by ``slots``
        ((B,), None: row ``b`` is slot ``b`` and the rows are all the
        slots), zeros on the rows that are ``fresh`` ((B,) bool or None:
        whose sequence starts here, whatever the slot held) —;
        ``store(lg, state, s, conv)`` puts what they leave back into a
        CARRIED pool (``carried``: the pool rides the layer scan,
        ``state``; else None: the forward collects the rows and
        ``write`` puts them). ``live`` (a carried pool, the rows all
        the slots: ``ops/gated_delta.py`` ``live_first`` of the rows
        that hold a sequence, computed once a step): the decode step
        runs as the Pallas kernel over the WHOLE state leaf in place,
        the idle rows' state unmoved — ``step(lg, state, *operands) ->
        (o, state)``, the operands the configuration's member of the
        family takes (a delta rule's ``q, k, v, g, beta``; a state-space
        layer's ``x, dt, A, B, C, D``); ``load`` then hands no ``S``
        (None) and ``store`` takes none."""
        def row(leaf, lg):
            if slots is None:
                a = jax.lax.dynamic_index_in_dim(leaf, lg, 0, False)
            else:
                # by (layer, slot) in ONE step over the flattened
                # leading axes: a ``leaf[lg]`` first is a copy of
                # the layer's whole slab (67 MB of state at 32
                # slots) to read one row of it (``HeadKV.window``)
                a = leaf.reshape((-1,) + leaf.shape[2:])[
                    lg * leaf.shape[1] + slots]
            return a if fresh is None else self.from_zeros(a, fresh)

        def load(lg, state, lp):
            pool = state if carried else kv_cache
            tail = row(pool["conv"], lg)
            return (None if live is not None else row(pool["s"], lg),
                    tail.reshape(tail.shape[0], -1,
                                 self.cfg.linear_channels))

        def store(lg, state, s, conv):
            new = {}
            for name, rows in (("s", s), ("conv", conv)):
                if rows is None:
                    continue
                leaf = state[name]
                rows = self._as_stored(name, rows, leaf)
                if slots is None:
                    new[name] = jax.lax.dynamic_update_index_in_dim(
                        leaf, rows, lg, 0)
                else:
                    new[name] = leaf.at[lg, slots].set(rows)
            return {**state, **new}

        def step(lg, state, *operands):
            if self.cfg.linear_decay == "ssd":
                from ..ops.ssd import ssd_step_kernel as kernel
            else:
                from ..ops.gated_delta import \
                    gated_delta_step_kernel as kernel
            o, s = kernel(*operands, live, state["s"], lg,
                          interpret=jax.default_backend() != "tpu")
            return o, {**state, "s": s}

        return (load, store if carried else None,
                step if live is not None else None)

    def step_kernel_supported(self) -> bool:
        from ..ops import ssd
        from ..ops.gated_delta import step_kernel_supported
        cfg = self.cfg
        if cfg.linear_decay == "ssd":
            return ssd.step_kernel_supported(
                cfg.linear_num_value_heads, cfg.linear_num_key_heads,
                cfg.linear_value_head_dim, cfg.linear_key_head_dim)
        return step_kernel_supported(cfg.linear_num_value_heads,
                                     cfg.linear_key_head_dim,
                                     cfg.linear_value_head_dim)

    # ---------------------------------------------------------------- write

    def write(self, kv_cache: KVCache, new_k, new_v, new_s, new_conv,
              pages, offsets: Optional[jax.Array] = None,
              slots: Optional[jax.Array] = None) -> KVCache:
        """The paged part's ``write`` of the attention layers' rows and,
        in the same step, the recurrent layers' state of the rows'
        ``slots`` ((B,); None: the rows are all the slots, in order):
        ``new_s`` (Lg, B, Hv, dk, dv) and ``new_conv`` (Lg, B, K - 1,
        channels) as the scan stacked them."""
        out = self.paged.write(self._paged(kv_cache), new_k, new_v, pages,
                               offsets)
        with jax.named_scope(self.scope):
            for name, rows in (("s", new_s), ("conv", new_conv)):
                leaf = kv_cache[name]
                rows = self._as_stored(name, rows, leaf)
                at = jnp.arange(rows.shape[1]) if slots is None else slots
                out[name] = leaf.at[:, at].set(rows)
        return out

    def insert_pages(self, kv_cache: KVCache, k_new, v_new, s_new, conv_new,
                     dest, slots: Optional[jax.Array] = None) -> KVCache:
        """The paged part's ``insert_pages`` of a prefilled bucket's
        rows, and the ONE sequence's state into its slot (``slots``
        (1,); None: 0)."""
        out = self.paged.insert_pages(self._paged(kv_cache), k_new, v_new,
                                      dest)
        at = jnp.zeros((1,), jnp.int32) if slots is None else slots
        for name, rows in (("s", s_new), ("conv", conv_new)):
            out[name] = kv_cache[name].at[:, at].set(
                self._as_stored(name, rows, kv_cache[name]))
        return out


def _paged_prefix_attention(q, k_self, v_self, kc, vc, ksc, vsc,
                            block_table, start, kv_valid_len, page: int,
                            cfg: LlamaConfig, block_pages: int = 8,
                            window: Optional[jax.Array] = None,
                            layer: jax.Array | int = 0,
                            scale: Optional[float] = None):
    """Chunk queries attend [pooled prefix] + [their own chunk], with the
    prefix STREAMED from the pool in ``block_pages``-page blocks under an
    online softmax.

    The former implementation gathered the whole window up front —
    (1, P*page, KV, hd) per layer, ~4 GB per tensor at 16k tokens on 7B —
    which capped chunked long-prompt serving far below the pool's own
    capacity. Block streaming bounds the transient to one block's K/V
    plus one (KV, G, C, block) score tile, independent of prefix length.

    q:            (1, C, H, hd) post-rope queries (C = chunk length)
    k/v_self:     (1, C, KV, hd) this chunk's post-rope K/V (NOT yet in
                  the pool — the pool's rows for these positions are
                  stale, so the self part computes in-register)
    kc/vc:        (L, N, KV, page, hd) the WHOLE pool, or one layer's
                  (N, KV, page, hd); int8 when ksc/vsc, the per-row
                  scales of the same leading axes, are given
    block_table:  (1, P) logical→physical window
    start:        () int32 — absolute position of the chunk's first row
                  (page-aligned); pool rows with logical position >=
                  start are masked (stale/future)
    kv_valid_len: (1,) int32 — start + valid tokens in this chunk
    window:       () int32 or None — this layer's window in keys (0 =
                  whole context): the query at position p attends keys
                  p - window < j <= p, and prefix blocks wholly behind
                  the FIRST query's window are skipped like those past
                  the prefix
    scale:        what the scores are multiplied by where the model
                  states it (``HeadKV.scale``); None = head_dim ** -0.5
    layer:        () int32 — the layer to read of a whole pool. A
                  block's pages are gathered by (layer, page) in ONE
                  step, over the pool's flattened leading axes: a
                  ``pool[layer]`` first is invariant in the block loop,
                  and XLA hoists it into a copy of the layer's whole
                  slab (2 x 142 MB a layer of a 1088-page pool) to read
                  eight pages of it
    Returns (1, C, H, hd) in q.dtype.
    """
    B, C, H, hd = q.shape
    KV = cfg.num_kv_heads
    G = H // KV
    scale = 1.0 / (hd ** 0.5) if scale is None else scale
    P = block_table.shape[1]
    nb = -(-P // block_pages)
    tbl = jnp.pad(block_table[0], (0, nb * block_pages - P))
    tbl = tbl + layer * kc.shape[-4]
    kc, vc = (a.reshape((-1,) + a.shape[-3:]) for a in (kc, vc))
    if ksc is not None:
        ksc, vsc = (a.reshape((-1,) + a.shape[-2:]) for a in (ksc, vsc))
    cd = q.dtype
    # operands stay in storage dtype into the MXU with f32 accumulation
    # (casting whole K/V blocks to f32 up front would double the
    # prefix stream's HBM bytes — the anti-pattern ops/attention.py's
    # chunked path documents avoiding); softmax state is f32.
    qf = q[0].reshape(C, KV, G, hd)
    tblk = block_pages * page
    rel = jnp.arange(C, dtype=jnp.int32)
    if window is not None:
        # first key each query attends (0 where the layer has no window)
        lo = jnp.where(window > 0, start + rel - window + 1, 0)  # (C,)

    def online(carry, s, mask, vb):
        """One online-softmax update. s: (KV, G, C, T) f32 scores,
        mask (C, T) or (T,); explicit zeroing of masked probabilities —
        relying on exp(-1e30 - m) underflow alone breaks the moment a
        stale pool row is non-finite (NaN * 0 = NaN)."""
        m, l, acc = carry
        mb = jnp.broadcast_to(mask, s.shape[-2:])[None, None]
        s = jnp.where(mb, s, -1e30)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1))
        alpha = jnp.exp(m - m_new)
        p = jnp.where(mb, jnp.exp(s - m_new[..., None]), 0.0)
        l_new = l * alpha + jnp.sum(p, axis=-1)
        acc_new = (acc * alpha[..., None]
                   + jnp.einsum("kgct,tkh->kgch", p.astype(cd), vb,
                                preferred_element_type=jnp.float32))
        return m_new, l_new, acc_new

    def dequant_block(pool, scales, pages):
        g = pool[pages]                         # (bp, KV, page, hd)
        if scales is not None:
            from ..ops.kv_quant import dequantize_rows
            g = dequantize_rows(g, scales[pages], cd)
        return g.swapaxes(1, 2).reshape(tblk, KV, hd).astype(cd)

    def block(carry, bi):
        def live(carry):
            pages = jax.lax.dynamic_slice(tbl, (bi * block_pages,),
                                          (block_pages,))
            kb = dequant_block(kc, ksc, pages)
            vb = dequant_block(vc, vsc, pages)
            t = bi * tblk + jnp.arange(tblk, dtype=jnp.int32)
            s = jnp.einsum("ckgh,tkh->kgct", qf, kb,
                           preferred_element_type=jnp.float32) * scale
            # prefix rows only: pool rows at/past `start` are stale
            # (this chunk's own rows land post-scan) — and every prefix
            # row is causally visible to every chunk query (t < start)
            mask = t < start
            # ... and the V rows no query may read are zeroed, not only
            # their probabilities: a block that holds the end of the
            # prefix also holds pages past it — this chunk's own, stale,
            # and past the extent the TRASH page, where the decode kernel
            # parks idle slots' rows beside whatever its scratch held.
            # One non-finite value there and 0 x NaN = NaN reaches every
            # query of the chunk through the PV product (PERF.md section
            # 7 row 1: a request answers with garbage from then on).
            vb = jnp.where(mask[:, None, None], vb, 0)
            if window is not None:
                mask = mask[None, :] & (t[None, :] >= lo[:, None])
            return online(carry, s, mask, vb)
        # blocks wholly past the prefix would be gathered then fully
        # masked — skip their HBM reads and matmuls at runtime; so
        # would blocks wholly behind the first query's window
        wanted = bi * tblk < start
        if window is not None:
            wanted = wanted & ((bi + 1) * tblk > lo[0])
        return jax.lax.cond(wanted, live, lambda c: c, carry), None

    m0 = jnp.full((KV, G, C), -1e30, jnp.float32)
    l0 = jnp.zeros((KV, G, C), jnp.float32)
    acc0 = jnp.zeros((KV, G, C, hd), jnp.float32)
    (m, l, acc), _ = jax.lax.scan(
        block, (m0, l0, acc0), jnp.arange(nb, dtype=jnp.int32))

    # the chunk itself, ALSO in key blocks — a dense (KV, G, C, C) f32
    # score tensor at C=2048 on 7B is 512 MB/layer, the transient the
    # chunked-attention machinery exists to avoid
    sb = min(C, 512)
    while C % sb:
        sb //= 2
    ks, vs = k_self[0], v_self[0]               # (C, KV, hd)

    def self_block(carry, si):
        kb = jax.lax.dynamic_slice(ks, (si * sb, 0, 0), (sb, KV, hd))
        vb = jax.lax.dynamic_slice(vs, (si * sb, 0, 0), (sb, KV, hd))
        tloc = si * sb + jnp.arange(sb, dtype=jnp.int32)
        s = jnp.einsum("ckgh,tkh->kgct", qf, kb,
                       preferred_element_type=jnp.float32) * scale
        ok = (tloc[None, :] <= rel[:, None]) \
            & ((start + tloc) < kv_valid_len[0])[None, :]
        if window is not None:
            ok = ok & ((start + tloc)[None, :] >= lo[:, None])
        return online(carry, s, ok, vb), None

    (m, l, acc), _ = jax.lax.scan(
        self_block, (m, l, acc), jnp.arange(C // sb, dtype=jnp.int32))
    # valid queries attend at least themselves (l > 0); PADDED rows past
    # kv_valid_len attend nothing — floor the denominator so they yield
    # zeros, not NaNs that would trip debug tooling downstream
    out = acc / jnp.maximum(l[..., None], 1e-30)
    # (KV, G, C, hd) -> (1, C, H, hd)
    return out.transpose(2, 0, 1, 3).reshape(1, C, H, hd).astype(q.dtype)


class LatentKV:
    """One latent row a token for all heads: ``{"c": (L, N, 1, page, R),
    "r": (L, N, 1, rope, page)}`` (module docstring). bf16 or float32
    rows only: an int8 latent pool is refused by name."""

    leaves = ("c", "r")

    def __init__(self, cfg: LlamaConfig, layers: Optional[int] = None):
        self.cfg = cfg
        self.n_layers = cfg.num_layers if layers is None else layers
        self.R, self.rope = cfg.kv_lora_rank, cfg.qk_rope_head_dim
        self.nope, self.vd = cfg.qk_nope_head_dim, cfg.v_head_dim

    # ---------------------------------------------------------------- build

    def init_dense(self, batch: int, max_len: int,
                   dtype: jnp.dtype = jnp.bfloat16) -> KVCache:
        L = self.n_layers
        return {"c": jnp.zeros((L, batch, max_len, self.R), dtype),
                "r": jnp.zeros((L, batch, max_len, self.rope), dtype)}

    def init_pool(self, n_pages: int, page_size: int,
                  dtype: jnp.dtype = jnp.bfloat16,
                  quantized: bool = False) -> KVCache:
        if quantized:
            raise NotImplementedError(
                "an int8 KV pool over a latent cache (kv_lora_rank) is "
                "not supported: the latent row is key and value at once "
                "and has one scale plane too few")
        L = self.n_layers
        return {"c": jnp.zeros((L, n_pages, 1, page_size, self.R), dtype),
                "r": jnp.zeros((L, n_pages, 1, self.rope, page_size),
                               dtype)}

    # ----------------------------------------------------------------- size

    def token_bytes(self, itemsize: int, quantized: bool = False) -> int:
        """Bytes a cached token a layer: the latent and the rotary key
        part, once for all heads."""
        if quantized:
            raise NotImplementedError("int8 KV over a latent cache")
        return (self.R + self.rope) * itemsize

    model_token_bytes = HeadKV.model_token_bytes

    @staticmethod
    def page_size(kv_cache: KVCache) -> int:
        return kv_cache["c"].shape[3]

    @staticmethod
    def quantized(kv_cache: KVCache) -> bool:
        return False

    def kernel_supported(self, page: int) -> bool:
        from ..ops.latent_attention import kernel_supported
        return kernel_supported(page, self.R, self.rope)

    def prefix_kernel_supported(self, page: int) -> bool:
        """Whether ``attend_prefix`` can run the chunk kernels
        (ops/chunk_attention.py) at this geometry: a head's ``nope`` key
        columns whole lanes beside the shared rotary part, or (192 + 64)
        whole lanes WITH it, the rotary part then copied into every
        head's keys of the chunk's own block (``_fold_shared``; the
        prefix kernel joins it to a head's keys in VMEM either way)."""
        from ..ops.chunk_attention import kernel_supported
        return kernel_supported(page, self.nope, self.vd, self.rope) \
            or kernel_supported(page, self.nope + self.rope, self.vd)

    @property
    def _fold_shared(self) -> bool:
        return self.nope % 128 != 0

    def pool_spec(self, mesh, quantized: bool = False) -> dict:
        """Replicated: the latent is common to all heads, so a ``tp``
        mesh has nothing of it to split (the engine refuses one by
        name)."""
        from jax.sharding import PartitionSpec as P
        return dict.fromkeys(self.leaves, P())

    # ---------------------------------------------------------------- write

    def write(self, kv_cache: KVCache, new_c: jax.Array, new_r: jax.Array,
              pages: jax.Array, offsets: Optional[jax.Array] = None
              ) -> KVCache:
        """``HeadKV.write`` for the latent leaves. Rows: new_c (L, B, S,
        R) and new_r (L, B, S, rope) to ``pages`` / ``offsets`` (B, S) —
        a latent row is one flat row of (N, page), its rotary part
        ``rope`` single values of (N, rope, page), a lane each. Whole
        pages: (L, C, R) and (L, C, rope), the latter turned."""
        L, N, _, page, R = kv_cache["c"].shape
        rope = self.rope
        pc, pr = kv_cache["c"], kv_cache["r"]
        with jax.named_scope("attn"):      # the KV write
            if offsets is None:
                cb = new_c.reshape(L, -1, 1, page, R)
                rb = new_r.reshape(L, -1, 1, page, rope).swapaxes(3, 4)
                return {"c": pc.at[:, pages].set(cb.astype(pc.dtype)),
                        "r": pr.at[:, pages].set(rb.astype(pr.dtype))}
            row = pages * page + offsets                        # (B, S)
            lane = ((pages[..., None] * rope + jnp.arange(rope)) * page
                    + offsets[..., None])                       # (B,S,rope)
            layer = jnp.arange(L)[:, None, None]
            c = pc.reshape(L, N * page, R).at[layer, row[None]].set(
                new_c.astype(pc.dtype)).reshape(pc.shape)
            r = pr.reshape(L, N * rope * page).at[
                layer[..., None], lane[None]].set(
                new_r.astype(pr.dtype)).reshape(pr.shape)
            return {"c": c, "r": r}

    def insert_pages(self, kv_cache: KVCache, c_new: jax.Array,
                     r_new: jax.Array, dest: jax.Array) -> KVCache:
        """``HeadKV.insert_pages``: dense rows (L, 1, S, R) and (L, 1, S,
        rope) into the pages ``dest``."""
        return self.write(kv_cache, c_new[:, 0], r_new[:, 0], dest)

    # ----------------------------------------------------------------- read

    def window(self, kv_cache: KVCache, layer, block_table):
        """One layer's slot windows gathered out of the whole pool by
        (layer, page): ``c`` (B, P*page, R) and ``k_r`` (B, P*page,
        rope)."""
        pc, pr = kv_cache["c"], kv_cache["r"]
        B, P = block_table.shape
        page = pc.shape[3]
        pages = block_table + layer * pc.shape[1]
        gc = pc.reshape((-1,) + pc.shape[3:])[pages]        # (B,P,page,R)
        gr = pr.reshape((-1,) + pr.shape[3:])[pages]        # (B,P,rope,page)
        return gc.reshape(B, P * page, self.R), \
            gr.swapaxes(2, 3).reshape(B, P * page, self.rope)

    def _split(self, q):
        return q[..., :self.nope], q[..., self.nope:]

    def attend_tokens(self, q, c, k_r, lp, positions, kv_valid_len):
        """Expanded attention over the tokens given (B, T = S): every
        head's keys and values from the latent through ``wk_b`` /
        ``wv_b``."""
        from ..ops.latent_attention import expanded_attention
        B, T, _ = c.shape
        H = self.cfg.num_heads
        k_nope = qmm(c, lp["wk_b"]).reshape(B, T, H, self.nope)
        v = qmm(c, lp["wv_b"]).reshape(B, T, H, self.vd)
        qn, qr = self._split(q)
        return expanded_attention(qn, qr, k_nope, k_r, v, positions,
                                  kv_valid_len, self.cfg.score_scale)

    def put_dense(self, lp, c, k_r, row_start):
        put = jax.vmap(
            lambda a, u, s: jax.lax.dynamic_update_slice(a, u, (s, 0)))
        return put(lp["cache_c"], c, row_start), \
            put(lp["cache_r"], k_r, row_start)

    def absorb(self, qn, lp):
        """The query carried into the latent space: q~_h = q_nope_h
        W_UK,h^T, (B, S, H, nope) -> (B, S, H, R)."""
        from ..ops.latent_attention import head_product
        return head_product(qn, lp["wk_b"], self.cfg.num_heads,
                            transposed=True)

    def attend_window(self, q, c, k_r, lp, kv_cache, layer, block_table,
                      rows, positions, kv_valid_len):
        """Absorbed attention over the slot's gathered window with the S
        current tokens joined in-register: each cached row is key and
        value; ``wv_b`` after the sum."""
        from ..ops.latent_attention import absorbed_attention, head_product
        qn, qr = self._split(q)
        gc, gr = self.window(kv_cache, layer, block_table)
        gc = gc.astype(q.dtype).at[rows, positions].set(c.astype(q.dtype))
        gr = gr.astype(q.dtype).at[rows, positions].set(
            k_r.astype(q.dtype))
        o_c = absorbed_attention(self.absorb(qn, lp), qr, gc, gr, positions,
                                 kv_valid_len, self.cfg.score_scale)
        return head_product(o_c, lp["wv_b"], self.cfg.num_heads)

    def attend_prefix(self, q, c, k_r, lp, kv_cache, block_table, start,
                      kv_valid_len, layer, block_pages: int = 4,
                      use_kernel: bool = False, keep=None):
        """A chunk's attention, EXPANDED: the chunk's own tokens and each
        ``block_pages``-page block of the prefix it reads back from the
        latent pool are taken through ``wk_b`` / ``wv_b`` (one expansion
        a block serves all C x H queries), under one online softmax.
        B = 1. Pool rows at or past ``start`` are stale or another
        slot's: masked, and zeroed BEFORE the expansion (the trash page
        may hold anything; ``HeadKV``'s reader says why).

        ``use_kernel``: the whole prefix is ONE call of the Pallas kernel
        ``chunk_attention_prefix`` (ops/chunk_attention.py) — the walk of
        the block table, the pages' fetch, the zeroing, the expansion of
        a head group's keys and values and the softmax state are the
        kernel's, nothing of a block crosses HBM but its latent rows and
        its rows of ``keep`` — and the chunk's own tokens, expanded here
        by XLA, are folded into the carry it returns by
        ``chunk_attention_update``, the (H, T, C) float32 scores on the
        chip. False is the same attention as jnp operations, a ``scan``
        over the prefix blocks with the gather, the zeroing and the
        expansion XLA's (the CPU, and what the kernels are held
        against).

        ``keep``: (prefix blocks' keys + C, C) bool or None, keys by
        rows — a query (column) attends a key only where it is set, the
        same for all heads (``SparseLatentKV``); the prefix part is
        indexed like the padded block table, the last C rows are the
        chunk's own keys."""
        from ..ops import chunk_attention as ca
        cfg = self.cfg
        _, C, H, _ = q.shape
        nope, vd, rope, R = self.nope, self.vd, self.rope, self.R
        pc, pr = kv_cache["c"], kv_cache["r"]
        page = pc.shape[3]
        scale = cfg.score_scale
        P = block_table.shape[1]
        nb = -(-P // block_pages)
        tbl = jnp.pad(block_table[0], (0, nb * block_pages - P))
        tbl = tbl + layer * pc.shape[1]
        pc = pc.reshape((-1,) + pc.shape[3:])               # (L*N, page, R)
        pr = pr.reshape((-1,) + pr.shape[3:])               # (L*N, rope, page)
        cd = q.dtype
        tblk = block_pages * page
        rel = jnp.arange(C, dtype=jnp.int32)

        def expand(cb):
            """Every head's keys (T, H * nope) and values (T, H * vd)."""
            return qmm(cb, lp["wk_b"]), qmm(cb, lp["wv_b"])

        # ``update``: one block of expanded keys and values folded into the
        # carry. The kernel builds its mask from three positions (the
        # block's first key, the limit past which keys are masked,
        # ``causal``); the jnp form takes the mask made.
        if use_kernel:
            qh = q[0].transpose(1, 0, 2)                    # (H, C, .)
            interp = jax.default_backend() != "tpu"
            # the prefix needs no ``update``: one kernel walks the table,
            # zeroes, expands and keeps the carry for all its blocks
            carry = ca.chunk_attention_prefix(
                qh, lp["wk_b"], lp["wv_b"], pc, pr, tbl, start, scale=scale,
                block_pages=block_pages, interpret=interp,
                keep=None if keep is None
                else keep[:nb * tblk].astype(jnp.float32))

            def update(carry, kb, rb, vb, mask, k0, limit, causal, kp=None):
                more = {}
                if self._fold_shared:   # [nope | rope] a head: whole lanes
                    T = kb.shape[0]
                    kb = jnp.concatenate(
                        [kb.reshape(T, H, nope),
                         jnp.broadcast_to(rb[:, None], (T, H, rope))],
                        axis=-1).reshape(T, H * (nope + rope))
                    rb = None
                if kp is not None:
                    more["keep"] = kp.astype(jnp.float32)
                return ca.chunk_attention_update(
                    qh, kb, vb.T, carry, k0, limit, start, scale=scale,
                    causal=causal, k_shared=rb, interpret=interp, **more)
        else:
            qn, qr = self._split(q[0])                      # (C, H, .)

            def update(carry, kb, rb, vb, mask, k0, limit, causal, kp=None):
                m, l, acc = carry
                if kp is not None:
                    mask = jnp.broadcast_to(mask, kp.shape[::-1]) & kp.T
                kb, vb = kb.reshape(-1, H, nope), vb.reshape(-1, H, vd)
                s = (jnp.einsum("chj,thj->hct", qn, kb,
                                preferred_element_type=jnp.float32)
                     + jnp.einsum("chr,tr->hct", qr, rb,
                                  preferred_element_type=jnp.float32)
                     ) * scale
                mb = jnp.broadcast_to(mask, s.shape[-2:])[None]
                s = jnp.where(mb, s, -1e30)
                m_new = jnp.maximum(m, jnp.max(s, axis=-1))
                alpha = jnp.exp(m - m_new)
                p = jnp.where(mb, jnp.exp(s - m_new[..., None]), 0.0)
                l_new = l * alpha + jnp.sum(p, axis=-1)
                acc_new = (acc * alpha[..., None]
                           + jnp.einsum("hct,thv->hcv", p.astype(cd), vb,
                                        preferred_element_type=jnp.float32))
                return m_new, l_new, acc_new

            def block(carry, bi):
                def live(carry):
                    pages = jax.lax.dynamic_slice(tbl, (bi * block_pages,),
                                                  (block_pages,))
                    t = bi * tblk + jnp.arange(tblk, dtype=jnp.int32)
                    mask = t < start
                    cb = jnp.where(
                        mask[:, None],
                        pc[pages].reshape(tblk, R).astype(cd), 0)
                    rb = jnp.where(
                        mask[:, None],
                        pr[pages].swapaxes(1, 2).reshape(tblk, rope)
                        .astype(cd), 0)
                    kb, vb = expand(cb)
                    kp = () if keep is None else (jax.lax.dynamic_slice(
                        keep, (bi * tblk, 0), (tblk, C)),)
                    return update(carry, kb, rb, vb, mask, bi * tblk, start,
                                  False, *kp)
                return jax.lax.cond(bi * tblk < start, live, lambda c: c,
                                    carry), None

            carry, _ = jax.lax.scan(
                block, (jnp.full((H, C), -1e30, jnp.float32),
                        jnp.zeros((H, C), jnp.float32),
                        jnp.zeros((H, C, vd), jnp.float32)),
                jnp.arange(nb, dtype=jnp.int32))

        sb = min(C, 512)
        while C % sb:
            sb //= 2
        ks, vs = expand(c[0])                               # (C, H * .)
        rs = k_r[0]

        def self_block(carry, si):
            kb = jax.lax.dynamic_slice(ks, (si * sb, 0), (sb, H * nope))
            vb = jax.lax.dynamic_slice(vs, (si * sb, 0), (sb, H * vd))
            rb = jax.lax.dynamic_slice(rs, (si * sb, 0), (sb, rope))
            tloc = si * sb + jnp.arange(sb, dtype=jnp.int32)
            ok = (tloc[None, :] <= rel[:, None]) \
                & ((start + tloc) < kv_valid_len[0])[None, :]
            kp = () if keep is None else (jax.lax.dynamic_slice(
                keep, (nb * tblk + si * sb, 0), (sb, C)),)
            return update(carry, kb, rb, vb, ok, start + si * sb,
                          kv_valid_len[0], True, *kp), None

        carry, _ = jax.lax.scan(
            self_block, carry, jnp.arange(C // sb, dtype=jnp.int32))
        if use_kernel:
            return ca.finish(carry, q.dtype)[None]
        m, l, acc = carry
        out = acc / jnp.maximum(l[..., None], 1e-30)        # (H, C, vd)
        return out.transpose(1, 0, 2)[None].astype(q.dtype)

    def kernel_attend(self, kv_cache: KVCache, block_table, pos_in_win,
                      write_page, write_offset, mesh, act_dtype):
        """The decode step's ``attend`` over the latent Pallas kernel
        (ops/latent_attention.py): the absorbed query against the pool's
        rows, the step's row appended inside the kernel, the pool in the
        layer scan's carry; ``wv_b`` after."""
        from ..ops.latent_attention import (head_product,
                                            latent_attention_decode)
        if mesh is not None and mesh.shape.get("tp", 1) > 1:
            raise NotImplementedError(
                "the latent decode kernel under a tp mesh is not supported")
        cfg = self.cfg
        dt = kv_cache["c"].dtype
        interp = jax.default_backend() != "tpu"

        def attend(q, c, k_r, lp, li, pool):
            qn, qr = self._split(q[:, 0])                   # (B, H, .)
            o_c, pc, pr = latent_attention_decode(
                self.absorb(qn[:, None], lp)[:, 0], qr, pool["c"],
                pool["r"], block_table, pos_in_win, c[:, 0].astype(dt),
                k_r[:, 0].astype(dt), write_page, write_offset, li,
                scale=cfg.score_scale, interpret=interp)
            attn = head_product(o_c[:, None], lp["wv_b"], cfg.num_heads)
            return attn, {"c": pc, "r": pr}

        return attend


class SparseLatentKV(LatentKV):
    """``LatentKV`` with an index cache and a masked read: ``{"c", "r"}``
    as there, ``"i": (Lf, N, 1, page, index_head_dim)`` over the full
    layers (module docstring)."""

    leaves = ("c", "r", "i")

    def __init__(self, cfg: LlamaConfig):
        super().__init__(cfg)
        self.K, self.di = cfg.index_topk, cfg.index_head_dim
        #: the model's full layers, in order: a full layer's place here
        #: is its layer of the ``"i"`` leaf
        self.full = tuple(i for i, f in enumerate(cfg.layer_index) if f)

    # ---------------------------------------------------------------- build

    def init_dense(self, batch: int, max_len: int,
                   dtype: jnp.dtype = jnp.bfloat16) -> KVCache:
        """The dense cache keeps an index row on EVERY layer (zeros on a
        shared one): its leaves are sliced a layer by the scan."""
        L = self.cfg.num_layers
        return dict(super().init_dense(batch, max_len, dtype),
                    i=jnp.zeros((L, batch, max_len, self.di), dtype))

    def init_pool(self, n_pages: int, page_size: int,
                  dtype: jnp.dtype = jnp.bfloat16,
                  quantized: bool = False) -> KVCache:
        pool = super().init_pool(n_pages, page_size, dtype, quantized)
        return dict(pool, i=jnp.zeros(
            (len(self.full), n_pages, 1, page_size, self.di), dtype))

    # ----------------------------------------------------------------- size

    def token_bytes(self, itemsize: int, quantized: bool = False) -> int:
        """Bytes a cached token on a FULL layer (a shared one has no
        index key): what a layer's gathered window is sized by."""
        return super().token_bytes(itemsize, quantized) + self.di * itemsize

    def index_token_bytes(self, itemsize: int) -> int:
        """Bytes a cached token's index keys take, all full layers."""
        return len(self.full) * self.di * itemsize

    def model_token_bytes(self, itemsize: int, quantized: bool = False
                          ) -> int:
        """Bytes a cached token, all layers: a latent row a layer, an
        index key a full layer."""
        return (self.cfg.num_layers
                * LatentKV.token_bytes(self, itemsize, quantized)
                + self.index_token_bytes(itemsize))

    @staticmethod
    def select_bytes(queries: int, keys: int) -> int:
        """What a full layer's selection holds at once for a chunk of
        ``queries`` over ``keys``: float32 scores, their sort keys, a
        running count and the masks (``select``), 16 B a pair."""
        return 16 * queries * keys

    def index_window_bytes(self, slots: int, keys: int, itemsize: int
                           ) -> int:
        """What the decode step over the kernel still gathers on a full
        layer: every slot's window of index keys (``index_window``) and
        the scores of all index heads over it at once
        (ops/sparse_index.py ``index_scores``)."""
        return slots * keys * (self.di * itemsize
                               + 4 * self.cfg.index_n_heads)

    def kernel_supported(self, page: int) -> bool:
        """Both kernels this cache has: the latent decode kernel and the
        chunk kernel."""
        return super().kernel_supported(page) \
            and self.prefix_kernel_supported(page)

    # ---------------------------------------------------------------- write

    def write(self, kv_cache: KVCache, new_c, new_r, new_i, pages,
              offsets: Optional[jax.Array] = None) -> KVCache:
        """``LatentKV.write`` and, in the same step, the full layers' index
        keys: ``new_i`` comes stacked over ALL layers (L, ...) as the scan
        gave it and the full layers' rows go to the same pages and
        offsets of the ``"i"`` leaf."""
        out = super().write(kv_cache, new_c, new_r, pages, offsets)
        pi = kv_cache["i"]
        Lf, N, _, page, di = pi.shape
        rows = new_i[jnp.asarray(self.full)].astype(pi.dtype)
        with jax.named_scope("attn_index"):     # the index key write
            if offsets is None:
                out["i"] = pi.at[:, pages].set(
                    rows.reshape(Lf, -1, 1, page, di))
            else:
                row = pages * page + offsets                    # (B, S)
                out["i"] = pi.reshape(Lf, N * page, di).at[
                    jnp.arange(Lf)[:, None, None], row[None]].set(
                    rows).reshape(pi.shape)
        return out

    def insert_pages(self, kv_cache: KVCache, c_new, r_new, i_new,
                     dest) -> KVCache:
        return self.write(kv_cache, c_new[:, 0], r_new[:, 0], i_new[:, 0],
                          dest)

    # ----------------------------------------------------------------- read

    def index_window(self, kv_cache: KVCache, at, block_table):
        """A full layer's slot windows of index keys, (B, P * page, di),
        gathered by (its place among the full layers, page)."""
        pi = kv_cache["i"]
        B, P = block_table.shape
        pages = block_table + at * pi.shape[1]
        return pi.reshape((-1,) + pi.shape[3:])[pages].reshape(
            B, P * pi.shape[3], self.di)

    def window_keys(self, kv_cache: KVCache, index: dict, block_table,
                    rows, positions, dtype) -> jax.Array:
        """``index_window`` of the layer, the current tokens' own keys set
        at their positions (they are not in the pool yet)."""
        gi = self.index_window(kv_cache, index["layer"], block_table)
        return gi.astype(dtype).at[rows, positions].set(
            index["k"].astype(dtype))

    def kernel_rows_read(self, contexts, page: int) -> int:
        """Cached rows ONE decode step over the kernel streams a layer for
        live rows of ``contexts`` tokens (the current one not cached
        yet): the kernel walks a row's pages in whole blocks."""
        from ..ops.latent_attention import _BLOCK_PAGES
        block = _BLOCK_PAGES * page
        return sum(-(-(c - 1) // block) for c in contexts) * block

    def select(self, index: dict, keys, valid: jax.Array) -> jax.Array:
        """The layer's keep mask (B, S, T): on a full layer the
        ``index_topk`` best of the ``valid`` (causal) keys by the
        indexer's scores over ``keys()`` (B, T, di), on a shared layer
        the mask carried from the full layer below — no score, no
        top-k, no read of an index key. A window no longer than
        ``index_topk`` keeps all that is valid without a score."""
        from ..ops.sparse_index import index_scores, topk_keep
        if valid.shape[-1] <= self.K:
            return valid

        def full(_):
            with jax.named_scope("attn_index"):
                scores = index_scores(index["q"], index["w"], keys())
            with jax.named_scope("attn_select"):
                return topk_keep(scores, valid, self.K)

        return jax.lax.cond(index["full"], full, lambda _: index["keep"],
                            None)

    def attend_tokens(self, q, c, k_r, lp, positions, kv_valid_len, index):
        """Expanded attention over the tokens given, each query over its
        kept keys. Returns ``(attn, keep)``."""
        from ..ops.latent_attention import _causal
        from ..ops.sparse_index import expanded_masked
        B, T, _ = c.shape
        H = self.cfg.num_heads
        valid = _causal(positions, kv_valid_len, T)[:, 0]
        keys = index.get("keys", index["k"])
        keep = self.select(index, lambda: keys.astype(index["q"].dtype),
                           valid)
        k_nope = qmm(c, lp["wk_b"]).reshape(B, T, H, self.nope)
        v = qmm(c, lp["wv_b"]).reshape(B, T, H, self.vd)
        qn, qr = self._split(q)
        return expanded_masked(qn, qr, k_nope.astype(q.dtype),
                               k_r.astype(q.dtype), v.astype(q.dtype), keep,
                               self.cfg.score_scale), keep

    def put_dense(self, lp, c, k_r, row_start, index):
        put = jax.vmap(
            lambda a, u, s: jax.lax.dynamic_update_slice(a, u, (s, 0)))
        return (*super().put_dense(lp, c, k_r, row_start),
                put(lp["cache_i"], index["k"].astype(lp["cache_i"].dtype),
                    row_start))

    def attend_window(self, q, c, k_r, lp, kv_cache, layer, block_table,
                      rows, positions, kv_valid_len, index):
        """``LatentKV.attend_window`` over the kept keys: the decode step
        and the verify forward. Returns ``(attn, keep)``."""
        from ..ops.latent_attention import _causal, head_product
        from ..ops.sparse_index import absorbed_masked
        qn, qr = self._split(q)
        gc, gr = self.window(kv_cache, layer, block_table)
        gc = gc.astype(q.dtype).at[rows, positions].set(c.astype(q.dtype))
        gr = gr.astype(q.dtype).at[rows, positions].set(
            k_r.astype(q.dtype))
        valid = _causal(positions, kv_valid_len, gc.shape[1])[:, 0]
        keep = self.select(index, lambda: self.window_keys(
            kv_cache, index, block_table, rows, positions, q.dtype), valid)
        o_c = absorbed_masked(self.absorb(qn, lp), qr, gc, gr, keep,
                              self.cfg.score_scale)
        return head_product(o_c, lp["wv_b"], self.cfg.num_heads), keep

    def kernel_attend(self, kv_cache: KVCache, block_table, pos_in_win,
                      write_page, write_offset, mesh, act_dtype):
        """``LatentKV.kernel_attend`` over the kept keys: the layer's keep
        mask is one more operand of the latent decode kernel, which
        streams each slot's live pages as before — no gathered window of
        latent rows. A full layer scores the slot windows of index keys
        read from the pool in the carry (the step's own key set at its
        position, as ``attend_window`` has it) and selects; a shared
        layer hands on the mask carried. The step's index key goes to
        the ``"i"`` leaf of the carried pool where the kernel puts the
        latent row — B rows scattered in place, to the trash page on a
        shared layer (no ``cond`` around the leaf: a branch that passes
        it through costs a copy of it). ``attend`` returns ``(attn,
        (pool, keep))`` (models/llama.py ``_run_stack``). A window no
        longer than ``index_topk`` keeps every causal key: the kernel
        without a mask, the mask carried untouched."""
        from ..ops.latent_attention import (
            head_product, latent_attention_decode_jit as
            latent_attention_decode)
        if mesh is not None and mesh.shape.get("tp", 1) > 1:
            raise NotImplementedError(
                "the latent decode kernel under a tp mesh is not supported")
        cfg = self.cfg
        dt = kv_cache["c"].dtype
        interp = jax.default_backend() != "tpu"
        B, W = block_table.shape
        Lf, N, _, page, di = kv_cache["i"].shape
        rows, pos = jnp.arange(B)[:, None], pos_in_win[:, None]
        # causal: the cached rows and the current token's own place
        valid = (jnp.arange(W * page, dtype=jnp.int32)[None, None]
                 <= pos_in_win[:, None, None])              # (B, 1, T)
        scored = W * page > self.K

        def attend(q, c, k_r, lp, li, pool, index):
            qn, qr = self._split(q[:, 0])                   # (B, H, .)
            keep, mask = index["keep"], {}
            if scored:
                keep = self.select(index, lambda: self.window_keys(
                    pool, index, block_table, rows, pos, q.dtype), valid)
                mask = dict(keep=keep[:, 0],
                            cur_keep=keep[rows, 0, pos][:, 0])
            o_c, pc, pr = latent_attention_decode(
                self.absorb(qn[:, None], lp)[:, 0], qr, pool["c"],
                pool["r"], block_table, pos_in_win, c[:, 0].astype(dt),
                k_r[:, 0].astype(dt), write_page, write_offset, li,
                scale=cfg.score_scale, interpret=interp, **mask)
            with jax.named_scope("attn_index"):     # the index key write
                at = (index["layer"] * N
                      + jnp.where(index["full"], write_page, 0)) * page \
                    + write_offset
                pi = pool["i"].reshape(Lf * N * page, di).at[at].set(
                    index["k"][:, 0].astype(dt)).reshape(pool["i"].shape)
            attn = head_product(o_c[:, None], lp["wv_b"], cfg.num_heads)
            return attn, ({"c": pc, "r": pr, "i": pi}, keep)

        return attend

    def prefix_keys(self, n_pages: int, page: int,
                    block_pages: int = 4) -> int:
        """The keys of a chunk's prefix as ``attend_prefix`` walks them:
        the block table padded to whole blocks."""
        return -(-n_pages // block_pages) * block_pages * page

    def attend_prefix(self, q, c, k_r, lp, kv_cache, block_table, start,
                      kv_valid_len, layer, index, block_pages: int = 4,
                      use_kernel: bool = False):
        """``LatentKV.attend_prefix`` over the kept keys; the mask is (C,
        prefix keys + C), a row a query. B = 1. Returns ``(attn,
        keep)``."""
        _, C, _, _ = q.shape
        P = block_table.shape[1]
        page = self.page_size(kv_cache)
        Tp = self.prefix_keys(P, page, block_pages)
        t = jnp.arange(Tp, dtype=jnp.int32)
        rel = jnp.arange(C, dtype=jnp.int32)
        own = (rel[None, :] <= rel[:, None]) \
            & ((start + rel) < kv_valid_len[0])[None, :]
        valid = jnp.concatenate(
            [jnp.broadcast_to(t[None, :] < start, (C, Tp)), own],
            axis=1)[None]                                   # (1, C, Tp + C)

        def keys():
            tbl = jnp.pad(block_table, ((0, 0), (0, Tp // page - P)))
            gi = self.index_window(kv_cache, index["layer"], tbl)
            return jnp.concatenate(
                [gi.astype(q.dtype), index["k"].astype(q.dtype)], axis=1)

        keep = self.select(index, keys, valid)
        attn = super().attend_prefix(
            q, c, k_r, lp, kv_cache, block_table, start, kv_valid_len,
            layer, block_pages, use_kernel, keep=keep[0].T)
        return attn, keep
