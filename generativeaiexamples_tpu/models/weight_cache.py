"""Converted-weight cache: orbax-backed, content-hash keyed.

The reference avoids rebuilding its TRT engines by caching them per
world-size/compute-capability directory, gated by a content hash of the
model dir (reference: model_server/model.py:140-145, 230-246). The TPU
stack's conversion is cheaper than an engine build but still real work —
torch-format parsing, key mapping, transpose/stack, quantization — and
it runs on every server start. This module is the SURVEY §5 "orbax-style
sharded weight cache": the CONVERTED (and, when requested, quantized)
parameter tree saved once in orbax's on-disk format, keyed by model
name + dtype + quant + checkpoint content hash, so a restart loads
arrays straight from disk and skips conversion entirely.

Layout: ``$GAIE_WEIGHT_CACHE_DIR (default ~/.cache/generativeaiexamples_tpu/
weights)/<identity>/tree``. Disable with ``GAIE_WEIGHT_CACHE=0``.
Writes are atomic (orbax finalizes into place), so a crashed save never
leaves a half-written tree that a later boot would trust.
"""

from __future__ import annotations

import logging
import os
import shutil
from typing import Any, Callable, Optional

logger = logging.getLogger("tpu-rag.weight_cache")


def enabled() -> bool:
    return os.environ.get("GAIE_WEIGHT_CACHE", "1") != "0"


def cache_root() -> str:
    return os.environ.get(
        "GAIE_WEIGHT_CACHE_DIR",
        os.path.join(os.path.expanduser("~"), ".cache",
                     "generativeaiexamples_tpu", "weights"))


def _tree_dir(identity: str) -> str:
    safe = identity.replace("/", "_")
    return os.path.join(cache_root(), safe, "tree")


def load(identity: str) -> Optional[Any]:
    """The cached param tree for this identity, or None (absent, disabled,
    or unreadable — an unreadable entry is dropped so the next save can
    replace it)."""
    if not enabled():
        return None
    path = _tree_dir(identity)
    if not os.path.isdir(path):
        return None
    try:
        import orbax.checkpoint as ocp
        with ocp.StandardCheckpointer() as ckptr:
            params = ckptr.restore(path)
        logger.info("weights loaded from cache %s", path)
        return params
    except Exception:  # noqa: BLE001 — cache must never block serving
        logger.exception("weight cache at %s unreadable; dropping it", path)
        shutil.rmtree(os.path.dirname(path), ignore_errors=True)
        return None


def save(identity: str, params: Any,
         prune_prefix: Optional[str] = None) -> bool:
    """Best-effort write; True when the tree landed.

    ``prune_prefix``: after a successful save, sibling cache entries
    whose identity starts with this prefix (same model/dtype/quant,
    OLD content hash) are deleted — a converted 7B tree is multi-GB, and
    without eviction every checkpoint update would leave a full copy in
    the cache forever."""
    if not enabled():
        return False
    path = _tree_dir(identity)
    try:
        import orbax.checkpoint as ocp
        os.makedirs(os.path.dirname(path), exist_ok=True)
        with ocp.StandardCheckpointer() as ckptr:
            ckptr.save(path, params, force=True)  # atomic finalize
        logger.info("weights cached at %s", path)
    except Exception:  # noqa: BLE001 — cache must never block serving
        logger.exception("weight cache save failed for %s", identity)
        shutil.rmtree(os.path.dirname(path), ignore_errors=True)
        return False
    if prune_prefix:
        keep = os.path.basename(os.path.dirname(path))
        prefix = prune_prefix.replace("/", "_")
        try:
            for entry in os.listdir(cache_root()):
                if entry.startswith(prefix) and entry != keep:
                    shutil.rmtree(os.path.join(cache_root(), entry),
                                  ignore_errors=True)
                    logger.info("pruned stale weight cache %s", entry)
        except OSError:
            pass
    return True


def cached_or_convert(identity: str, convert: Callable[[], Any],
                      prune_prefix: Optional[str] = None
                      ) -> tuple[Any, bool]:
    """(params, from_cache): load the cached tree, or run ``convert()``
    and cache its result. The convert callable must return the FINAL
    served tree (post-quantization) — the identity string encodes the
    quantization mode, so a cached int8 tree is never served as raw."""
    params = load(identity)
    if params is not None:
        return params, True
    params = convert()
    save(identity, params, prune_prefix=prune_prefix)
    return params, False
