"""Operations and bytes NEEDED by a latent-cache expert model whose
residual path is ``hc_mult`` streams mixed by hyper-connections, from
the ``model`` group of a configuration file alone — never read from the
program. ``costs_latent.py`` counts the block (the attention matrices as
stored, one latent row a cached token, the held experts the rows are
expected to touch — every expert here, where ``experts_held`` is absent
—, the shared expert, the dense layers, the tail); this adds the
residual path. Named for what they are and for no model, the keys read
beside ``costs_latent.py``'s:

    hc_mult (n)             streams a token; 0 or absent: nothing is added
    hidden_size (C)         a stream's width

What a token NEEDS of the chip's memory, a layer: its stream read once
and written once, ``2 n C`` values — the layer scan's carry lives in
HBM. Nothing else is counted as needed: a sublayer's input and output
(``C`` each) and the stream between a layer's two sublayers can stay on
the chip, and at a 512-token program the chip's compiler keeps them
there (memory space 1 in its optimised HLO). The count the issue gave,
``3 n C + 2 C`` a SUBLAYER (each side reading the stream from HBM and
writing its result back), is what two fused kernels a sublayer would
move and read 116-126 % against the traced scopes (chip, PR 42): no
bound, and not counted here. The
model's first stream is written once and its last read once a token:
``2 n C`` values a program more. ``phi`` — (n C) x (n^2 + 2n), bf16 — is
read once a sublayer a PROGRAM. Operations, a token a sublayer: ``2 n C
(n^2 + 2n) + 2 n^2 C + 4 n C`` (the projection on ``phi``; ``H_res . X``;
``H_pre . X`` and ``H_post^T y``). Values are the activations' two bytes.
What it does not count: the coefficient path's own arithmetic (a few
thousand operations a token on 24 values) and the float32 ``alpha`` /
``b``. The least time is taken against the chip's MATMUL peak
(``costs.least_seconds``), which the mixes' elementwise float32
arithmetic cannot reach: a share of this roofline says how far the
streams are from costing only their carry's two passes.
"""

from __future__ import annotations

from benchmarks.harness import costs_latent

ACT_BYTES = 2


def streams(m: dict) -> int:
    return int(m.get("hc_mult") or 0)


def layer_values(m: dict) -> int:
    """Stream values ONE layer must move through HBM for ONE token: its
    carry in and out."""
    return 2 * streams(m) * m["hidden_size"]


def sublayer_flops(m: dict) -> int:
    n, C = streams(m), m["hidden_size"]
    return 2 * n * C * (n * n + 2 * n) + 2 * n * n * C + 4 * n * C if n else 0


def phi_bytes(m: dict) -> int:
    """One sublayer's ``phi`` as stored (bf16)."""
    n, C = streams(m), m["hidden_size"]
    return 2 * n * C * (n * n + 2 * n)


def hc_stage(m: dict, tokens: float, programs: float = 1.0) -> dict:
    """The residual path of ``tokens`` tokens run as ``programs``
    programs: every layer's carry in and out, the first stream's write
    and the last one's read, ``phi`` once a sublayer a program; the
    operations of every sublayer."""
    n, C, L = streams(m), m["hidden_size"], m["num_layers"]
    if not n:
        return {"bytes": 0.0, "flops": 0.0}
    values = tokens * (L * layer_values(m) + 2 * n * C)
    return {"bytes": values * ACT_BYTES + programs * 2 * L * phi_bytes(m),
            "flops": tokens * (2 * L * sublayer_flops(m) + n * C)}


def decode_step(m: dict, quant: str, rows: float, kv_tokens: float,
                kv_dtype_bytes: int = 2) -> dict:
    """``costs_latent.decode_step`` plus the residual path of ``rows``
    tokens, one program."""
    step = costs_latent.decode_step(m, quant, rows, kv_tokens,
                                    kv_dtype_bytes)
    hc = hc_stage(m, rows)
    return {**step, "hc_bytes": hc["bytes"],
            "weight_bytes": step["weight_bytes"] + hc["bytes"],
            "bytes": step["bytes"] + hc["bytes"],
            "flops": step["flops"] + hc["flops"]}
