"""Fields of the engine's per-round records (``obs/rounds.py``) that only
a round with decode steps carries, over the window's rounds that decoded
(``decode_slots`` > 0). ``round_fields`` means over every round: a
prefill-only round holds 0 there and would halve a mean where half the
rounds are prefill-only (chip, PR 28).

args: ``field`` (a numeric RoundRecord attribute), ``per``
  "round"  mean over the decoding rounds (a field that is already a mean
           over the round's steps)
  "step"   sum over the decoding rounds / their decode steps (a field
           summed over the round's steps)
Returns None where no decoding round carries the field (a program
without it, or a window that never decoded).
"""


def read(ctx, field, per="round"):
    rounds = [r for r in ctx.rounds or []
              if hasattr(r, field) and getattr(r, "decode_slots", 0) > 0]
    if not rounds:
        return None
    total = sum(getattr(r, field) for r in rounds)
    if per == "round":
        return total / len(rounds)
    if per == "step":
        steps = sum(r.decode_steps for r in rounds)
        return total / steps if steps else None
    raise ValueError(f"decode_round_fields does not know per {per!r}")
