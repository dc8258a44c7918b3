"""Embedding service: jit batch encoder with passage/query modes.

Replaces the reference's embedding stack — HuggingFaceEmbeddings on cuda:0
(reference: common/utils.py:270-297) and the NeMo retriever's
``input_type`` passage/query switch
(reference: integrations/langchain/embeddings/nemo_embed.py:96-102) — with
a single jit-compiled encoder on TPU. Batches are padded to fixed buckets so
XLA compiles once per bucket.

The e5 convention: texts are prefixed "query: " / "passage: " before
encoding, then mean-pooled and L2-normalized.
"""

from __future__ import annotations

import hashlib
from typing import Optional, Sequence

import numpy as np

from ..models.configs import ENCODER_REGISTRY, EncoderConfig
from ..models.tokenizer import Tokenizer, get_tokenizer


class EmbeddingService:
    """Batched on-device text embedding."""

    def __init__(self, params, cfg: EncoderConfig, tokenizer: Tokenizer,
                 max_length: int = 512, batch_buckets: Sequence[int] = (1, 8, 32),
                 seq_buckets: Sequence[int] = (128, 512),
                 normalize: bool = True):
        import jax
        import jax.numpy as jnp

        from ..models import encoder as enc

        self.cfg = cfg
        self.tokenizer = tokenizer
        self.max_length = min(max_length, cfg.max_position_embeddings)
        self.batch_buckets = tuple(sorted(batch_buckets))
        # Sequence buckets: a chat query is ~20 tokens — padding it to the
        # passage length (512) made every query pay a full-length encoder
        # pass on the TTFT-critical retrieve.
        self.seq_buckets = tuple(sorted(
            {min(s, self.max_length) for s in seq_buckets}
            | {self.max_length}))
        self.normalize = normalize
        self.params = params

        def encode_fn(params, packed):
            # tokens and mask ride ONE transfer: packed (2, B, S) int32 —
            # one host->device hop per call instead of two.
            tokens, mask = packed[0], packed[1]
            hidden = enc.apply(params, cfg, tokens, mask)
            return enc.mean_pool(hidden, mask, normalize=normalize)

        self._encode = jax.jit(encode_fn)
        self._jnp = jnp

    # The e5 prefix convention (also what the reference's NeMo embedder maps
    # its passage/query input_type onto).
    def embed_documents(self, texts: Sequence[str]) -> np.ndarray:
        return self._embed([f"passage: {t}" for t in texts])

    def embed_query(self, text: str) -> np.ndarray:
        return self._embed([f"query: {text}"])[0]

    def embed_queries(self, texts: Sequence[str]) -> np.ndarray:
        """Batched query-mode embedding (one bucketed dispatch, not one
        device round-trip per text)."""
        return self._embed([f"query: {t}" for t in texts])

    @property
    def dim(self) -> int:
        return self.cfg.hidden_size

    def _bucket(self, n: int) -> int:
        for b in self.batch_buckets:
            if n <= b:
                return b
        return self.batch_buckets[-1]

    def _embed(self, texts: Sequence[str]) -> np.ndarray:
        out = np.zeros((len(texts), self.cfg.hidden_size), np.float32)
        maxb = self.batch_buckets[-1]
        for start in range(0, len(texts), maxb):
            chunk = texts[start:start + maxb]
            out[start:start + len(chunk)] = self._embed_chunk(chunk)
        return out

    def _embed_chunk(self, texts: Sequence[str]) -> np.ndarray:
        import time

        from ..obs.tracing import record_stage
        jnp = self._jnp
        B = self._bucket(len(texts))
        encoded = [self.tokenizer.encode(t)[:self.max_length] for t in texts]
        longest = max((len(ids) for ids in encoded), default=1)
        S = next(s for s in self.seq_buckets if longest <= s)
        packed = np.zeros((2, B, S), np.int32)
        for i, ids in enumerate(encoded):
            packed[0, i, :len(ids)] = ids
            packed[1, i, :len(ids)] = 1
        t0 = time.monotonic()
        emb = self._encode(self.params, jnp.asarray(packed))
        t1 = time.monotonic()
        out = np.asarray(emb)[:len(texts)]
        record_stage("embed_dispatch", t1 - t0)
        record_stage("embed_readback", time.monotonic() - t1)
        return out


class HashEmbedder:
    """Deterministic no-model embedder for tests and air-gapped dev.

    The 'fake engine' the reference made trivial but never shipped
    (SURVEY.md §4: the model_engine enum invites a fake). Embeds by hashing
    character n-grams, so similar texts get similar vectors.
    """

    def __init__(self, dim: int = 64):
        self._dim = dim

    @property
    def dim(self) -> int:
        return self._dim

    def _vec(self, text: str) -> np.ndarray:
        v = np.zeros(self._dim, np.float32)
        t = text.lower()
        for n in (3, 4):
            for i in range(max(0, len(t) - n + 1)):
                gram = t[i:i + n]
                h = int.from_bytes(
                    hashlib.md5(gram.encode()).digest()[:8], "little")
                v[h % self._dim] += 1.0
        norm = np.linalg.norm(v)
        return v / norm if norm > 0 else v

    def embed_documents(self, texts: Sequence[str]) -> np.ndarray:
        return np.stack([self._vec(f"passage: {t}") for t in texts])

    def embed_query(self, text: str) -> np.ndarray:
        return self._vec(f"passage: {text}")

    def embed_queries(self, texts: Sequence[str]) -> np.ndarray:
        return np.stack([self.embed_query(t) for t in texts])


def get_embedder(model_engine: str = "tpu-jax",
                 model_name: str = "intfloat/e5-large-v2",
                 checkpoint_path: Optional[str] = None,
                 dim: int = 64):
    """Factory, parity with ``get_embedding_model``
    (reference: common/utils.py:270-297). Engines: 'tpu-jax' (on-device
    encoder; random weights unless checkpoint_path), 'hash' (test double).
    """
    if model_engine == "hash":
        return HashEmbedder(dim=dim)
    if model_engine == "tpu-jax":
        import os

        import jax

        from ..models import encoder as enc
        from ..utils.errors import ConfigError

        if model_name not in ENCODER_REGISTRY:
            raise ConfigError(
                f"unknown encoder model {model_name!r}; known: "
                f"{sorted(ENCODER_REGISTRY)}")
        cfg = ENCODER_REGISTRY[model_name]
        if checkpoint_path:
            if not os.path.isdir(checkpoint_path):
                raise ConfigError("checkpoint_path must be a directory")
            from ..models.import_hf import _iter_safetensors
            params = enc.params_from_named_tensors(
                _iter_safetensors(checkpoint_path), cfg)
            tok = get_tokenizer(checkpoint_path)
        else:
            params = enc.init_params(cfg, jax.random.key(0))
            tok = get_tokenizer("byte")
        return EmbeddingService(params, cfg, tok)
    raise ValueError(f"unknown embedding engine {model_engine!r}")
