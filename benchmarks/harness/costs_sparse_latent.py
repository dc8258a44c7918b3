"""Operations and bytes a decode step NEEDS of a model with a latent
cache, an expert share and LEARNED SPARSE ATTENTION, from the ``model``
group of a configuration file alone — never read from the program, and
the same work whatever implements it: a read that gathers the chosen
rows and one that walks every cached row under a mask are held to the
SAME least, the gathered form's, so a masked path reads low on it.
``costs_latent.py`` counts every cached row of a context: wrong for such
a model. Named for what they are and for no model, the keys read beside
``costs_latent.py``'s:

    index_topk              the cached tokens a query's attention reads:
                            min(context, index_topk) a row a layer
    index_n_heads, index_head_dim   the indexer of a FULL layer: its
                            matrices q_rank x heads x dim, hidden x dim
                            and hidden x heads (bf16 as stored, never
                            quantized), one key of ``index_head_dim``
                            values a cached token
    index_layers            one 0/1 a layer, or a period repeated over
                            the layers (absent = all 1): which are full

A decode step reads: every attention matrix as stored and a full layer's
indexer matrices; ``index_head_dim`` values a cached token a FULL layer
(every index key of the live contexts, once); (rank + rope) values a
CHOSEN token a layer, once for all heads; a new row a sequence; the
router's columns, the held experts the rows are expected to touch, the
shared expert, the dense layers' MLP and the tail as ``costs_latent.py``
and ``costs.py`` count them. It computes two operations a weight a row
for the matrices a row passes through, 2 x heads x dim operations a
cached token a full layer for the index scores, and the absorbed
attention's 2 x ((rank + rope) + rank) x heads operations a CHOSEN token
a layer. The two context sums come from the contexts themselves:
``indexed`` = their sum, ``selected`` = the sum of min(context,
index_topk). What it does not count: the selection's comparisons, norm
weights, the embedding rows' source.
"""

from __future__ import annotations

from benchmarks.harness import costs, costs_latent
from benchmarks.harness.costs import _wbytes


def full_layers(m: dict) -> int:
    """How many of the model's layers have the indexer."""
    pattern = list(m.get("index_layers") or [1])
    return sum(int(pattern[i % len(pattern)])
               for i in range(m["num_layers"]))


def index_matrices(m: dict) -> list:
    """A full layer's indexer matrices as (rows, cols), bf16."""
    D, Rq = m["hidden_size"], m["q_lora_rank"]
    Hi, di = m["index_n_heads"], m["index_head_dim"]
    return [(Rq, Hi * di), (D, di), (D, Hi)]


def selected_tokens(m: dict, contexts) -> float:
    """Cached tokens a layer's attention reads for rows with these
    contexts: each row's min(context, index_topk)."""
    return float(sum(min(c, m["index_topk"]) for c in contexts))


def attn_stage(m: dict, rows: float, indexed: float, selected: float,
               kv_dtype_bytes: int = 2) -> dict:
    """The indexer, the selection and the sparse read over all layers
    (the program's scopes attn_index + attn_select + attn)."""
    L, Lf, H = m["num_layers"], full_layers(m), m["num_heads"]
    n = costs_latent.kv_values_per_token(m)
    Hi, di = m["index_n_heads"], m["index_head_dim"]
    weights = sum(r * c for r, c in index_matrices(m))
    return {
        "bytes": Lf * (2 * weights + (indexed + rows) * di * kv_dtype_bytes)
        + L * (selected + rows) * n * kv_dtype_bytes,
        "flops": Lf * (rows * 2 * weights + 2 * Hi * di * indexed)
        + L * 2 * (n + m["kv_lora_rank"]) * H * selected}


def decode_stage(m: dict, quant: str, stage: str, rows: float,
                 indexed: float, selected: float,
                 kv_dtype_bytes: int = 2) -> dict:
    """As ``costs_latent.decode_stage``, the ``attn`` stage counted over
    the index keys of the live contexts and the CHOSEN rows."""
    if stage == "attn":
        return attn_stage(m, rows, indexed, selected, kv_dtype_bytes)
    return costs_latent.decode_stage(m, quant, stage, rows, indexed,
                                     kv_dtype_bytes)


def decode_step(m: dict, quant: str, rows: float, indexed: float,
                selected: float, kv_dtype_bytes: int = 2) -> dict:
    """As ``costs_latent.decode_step``, for one decode step of ``rows``
    sequences whose contexts sum to ``indexed`` and whose chosen rows sum
    to ``selected``."""
    L, D = m["num_layers"], m["hidden_size"]
    attn = costs_latent.attn_matrices(m)
    attn_b = L * sum(_wbytes(r, c, quant) for r, c in attn)
    attn_f = rows * L * sum(2 * r * c for r, c in attn)
    parts = [decode_stage(m, quant, s, rows, indexed, selected,
                          kv_dtype_bytes) for s in costs.STAGES]
    index_b = full_layers(m) * 2 * sum(r * c for r, c in index_matrices(m))
    kv_bytes = parts[0]["bytes"] - index_b
    weight_bytes = attn_b + index_b + parts[1]["bytes"] \
        + parts[2]["bytes"] + rows * 2 * D
    return {"weight_bytes": weight_bytes, "kv_bytes": kv_bytes,
            "bytes": weight_bytes + kv_bytes,
            "flops": attn_f + sum(p["flops"] for p in parts)}
