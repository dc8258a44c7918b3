"""Fused on-device RAG admission: the whole retrieve->prompt->prefill hot
path as ONE XLA dispatch.

The reference's QA chatbot crosses three process boundaries on its hot
path — embed (GPU), Milvus search (gRPC), Triton prefill (gRPC)
(reference: RetrievalAugmentedGeneration/common/server.py:121-142 and
examples/developer_rag/chains.py:101-127). The host round trips between
them are pure latency: each blocking device<->host sync is a round
trip, and a chatbot TTFT pays them twice (embedding readback, then
first-token readback).

TPU-native answer: keep the corpus ON the device and compile the chain
itself into the admission program —

  query tokens ──► e5 encoder ──► dot-product top-k over the corpus
      ──► token-space prompt assembly (template + retrieved chunks)
      ──► prefill + sample + KV-insert (the engine's fused admission)

One host->device transfer in (the query's tokens, both vocabularies),
one device->host readback out (first token + assembled length + doc
ids). Retrieval context never touches the host.

Token-space assembly note: chunk token ids are concatenated at chunk
boundaries instead of re-tokenizing the joined string, so a BPE merge
that would span a boundary ("...end" + "\\n\\nThe...") stays split. The
token sequences differ from the host path only at those joins — the
rendered text is identical.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Sequence

import numpy as np


@dataclass(frozen=True)
class FusedRagSpec:
    """Static geometry + template tokens for the fused program.

    Prompt layout: ``prefix ⧺ [sep? doc_i]* ⧺ mid ⧺ question ⧺ suffix``
    (sep before every doc but the first — the token-space analogue of
    "\\n\\n".join). All lengths are compile-time constants.
    """
    prefix_ids: tuple          # template up to {context_str} (incl. BOS)
    sep_ids: tuple             # joiner between retrieved chunks
    mid_ids: tuple             # between {context_str} and {query_str}
    suffix_ids: tuple          # template tail after {query_str}
    top_k: int = 4             # reference: chains.py:117 top-4
    ctx_budget: int = 1500     # reference: common/utils.py:91 token cap
    bucket: int = 1024         # assembled-prompt static length
    chunk_tokens: int = 256    # per-chunk token capacity (C)
    q_bucket: int = 64         # question token capacity (LLM vocab)
    enc_bucket: int = 128      # question token capacity (encoder vocab)


def build_prompt_parts(rag_template: str, tokenizer) -> dict:
    """Split the RAG template at its placeholders and tokenize each part
    (prefix gets the BOS). Sentinel-based so any template text works."""
    probe = rag_template.format(context_str="\x00", query_str="\x01")
    prefix, rest = probe.split("\x00", 1)
    mid, suffix = rest.split("\x01", 1)
    return {
        "prefix_ids": tuple(tokenizer.encode(prefix, add_bos=True)),
        "sep_ids": tuple(tokenizer.encode("\n\n", add_bos=False)),
        "mid_ids": tuple(tokenizer.encode(mid, add_bos=False)),
        "suffix_ids": tuple(tokenizer.encode(suffix, add_bos=False)),
    }


class FusedRag:
    """Holds the encoder params, the device-resident corpus, and the
    assembly function; the engine jits it fused with its admission."""

    def __init__(self, enc_params, enc_cfg, spec: FusedRagSpec):
        import jax.numpy as jnp
        self.enc_params = enc_params
        self.enc_cfg = enc_cfg
        self.spec = spec
        self.corpus = {
            "emb": jnp.zeros((8, enc_cfg.hidden_size), jnp.float32),
            "toks": jnp.zeros((8, spec.chunk_tokens), jnp.int32),
            "lens": jnp.zeros((8,), jnp.int32),
            "n": jnp.int32(0),
        }

    # --------------------------------------------------------- corpus

    def set_corpus(self, emb: np.ndarray, toks: np.ndarray,
                   lens: np.ndarray) -> None:
        """Upload the retrieval corpus. Capacity pads to the next power
        of two so incremental ingest reuses compiled programs."""
        import jax
        import jax.numpy as jnp
        n, d = emb.shape
        cap = 8
        while cap < n:
            cap *= 2
        C = self.spec.chunk_tokens
        emb_p = np.zeros((cap, d), np.float32)
        emb_p[:n] = emb
        toks_p = np.zeros((cap, C), np.int32)
        toks_p[:n] = toks[:, :C]
        lens_p = np.zeros((cap,), np.int32)
        lens_p[:n] = np.minimum(lens, C)
        self.corpus = {
            "emb": jax.device_put(jnp.asarray(emb_p)),
            "toks": jax.device_put(jnp.asarray(toks_p)),
            "lens": jax.device_put(jnp.asarray(lens_p)),
            "n": jnp.int32(n),
        }

    # ------------------------------------------------------- assembly

    def assemble(self, enc_params, corpus, q_enc, q_llm, q_llm_len):
        """Device-side: embed the query, pick top-k chunks under the
        token budget, scatter template + chunks + question into one
        (bucket,) token row. Returns (tokens, length, top_ids).

        ``enc_params`` is an explicit argument (not read from self): the
        engine jits this composed with its admission program, and state
        read through ``self`` would leak tracers across traces."""
        import jax
        import jax.numpy as jnp

        from ..models import encoder as enc

        spec = self.spec
        S = spec.bucket
        K = spec.top_k
        C = spec.chunk_tokens

        hidden = enc.apply(enc_params, self.enc_cfg,
                           q_enc[0][None], q_enc[1][None])
        qvec = enc.mean_pool(hidden, q_enc[1][None], normalize=True)[0]

        emb = corpus["emb"]
        scores = emb @ qvec.astype(emb.dtype)                   # (Ncap,)
        live = jnp.arange(emb.shape[0]) < corpus["n"]
        scores = jnp.where(live, scores, -jnp.inf)
        _, top_ids = jax.lax.top_k(scores, K)
        picked = jnp.arange(K) < jnp.minimum(K, corpus["n"])
        dlens = jnp.where(picked, corpus["lens"][top_ids], 0)   # (K,)
        dtoks = corpus["toks"][top_ids]                         # (K, C)

        sep_len = len(spec.sep_ids)
        pre_len = len(spec.prefix_ids)
        # context budget: keep the leading run of docs that fits
        # (reference: LimitRetrievedNodesLength, common/utils.py:96-118)
        costs = jnp.where(dlens > 0,
                          dlens + jnp.where(jnp.arange(K) > 0, sep_len, 0),
                          0)
        keep = (jnp.cumsum(costs) <= spec.ctx_budget) & (dlens > 0)
        costs = jnp.where(keep, costs, 0)
        doc_off = pre_len + jnp.concatenate(
            [jnp.zeros((1,), jnp.int32),
             jnp.cumsum(costs)[:-1].astype(jnp.int32)])
        total_ctx = jnp.sum(costs)

        out = jnp.zeros((S,), jnp.int32)
        DROP = S  # out-of-range index -> scatter mode="drop"

        def place(out, ids, offset, valid_len, on):
            """Scatter a static token tuple / padded row at a dynamic
            offset; positions beyond valid_len (or when not on) drop."""
            ids = jnp.asarray(ids, jnp.int32)
            pos = jnp.arange(ids.shape[0], dtype=jnp.int32)
            idx = jnp.where(on & (pos < valid_len), offset + pos, DROP)
            return out.at[idx].set(ids, mode="drop")

        out = place(out, spec.prefix_ids, jnp.int32(0),
                    jnp.int32(pre_len), jnp.bool_(True))
        for i in range(K):
            if i > 0 and sep_len:
                out = place(out, spec.sep_ids, doc_off[i],
                            jnp.int32(sep_len), keep[i])
            tok_off = doc_off[i] + (sep_len if i > 0 else 0)
            out = place(out, dtoks[i], tok_off, dlens[i], keep[i])

        mid_off = pre_len + total_ctx
        out = place(out, spec.mid_ids, mid_off, jnp.int32(len(spec.mid_ids)),
                    jnp.bool_(True))
        q_off = mid_off + len(spec.mid_ids)
        out = place(out, q_llm, q_off, q_llm_len, jnp.bool_(True))
        suf_off = q_off + q_llm_len
        out = place(out, spec.suffix_ids, suf_off,
                    jnp.int32(len(spec.suffix_ids)), jnp.bool_(True))
        length = jnp.minimum(suf_off + len(spec.suffix_ids), S)
        return out, length.astype(jnp.int32), top_ids.astype(jnp.int32)


def corpus_rows(texts: Sequence[str], tokenizer, chunk_tokens: int):
    """Host-side: tokenize chunk texts (no BOS) into padded (N, C) rows
    for ``FusedRag.set_corpus``."""
    n = len(texts)
    toks = np.zeros((n, chunk_tokens), np.int32)
    lens = np.zeros((n,), np.int32)
    for i, t in enumerate(texts):
        ids = tokenizer.encode(t, add_bos=False)[:chunk_tokens]
        toks[i, :len(ids)] = ids
        lens[i] = len(ids)
    return toks, lens
