"""What one run leaves behind for the metric arithmetic and the
per-layer readers, and the end-to-end metrics themselves."""

from __future__ import annotations

import dataclasses
import importlib
import re
from typing import Optional

from . import stats as st


@dataclasses.dataclass
class Context:
    cell: object                       # spec.Cell
    rows: list                         # loadgen.Row, every request sent
    t0: float                          # window start (monotonic)
    t_end: float                       # window end
    drain_limit_s: float
    stats0: Optional[dict] = None      # engine.stats at window start
    stats1: Optional[dict] = None      # ... and end
    rounds: Optional[list] = None      # RoundRecords begun in the window
    trace: object = None               # trace.TraceReduction
    trace_t0: Optional[float] = None   # traced interval (monotonic)
    trace_t1: Optional[float] = None
    trace_rounds: Optional[list] = None
    compiles_in_window: Optional[list] = None
    engine_report: Optional[dict] = None
    peaks: Optional[dict] = None
    notes: dict = dataclasses.field(default_factory=dict)

    @property
    def window_s(self) -> float:
        return self.t_end - self.t0

    # -------------------------------------------------- request outcomes

    def ok(self, row) -> bool:
        """Finished correctly: ran to exactly the length asked."""
        return (row.error is None and row.stream.finish_reason == "length"
                and row.tokens == row.request.max_tokens)

    def failed_rows(self) -> list:
        return [r for r in self.rows if not self.ok(r)]

    # ------------------------------------------------ end-to-end metrics

    def ttft_ms(self) -> list:
        """Per request due in the window: first token - DUE instant; a
        request that failed, was shed or never finished is a miss."""
        return [(r.first_token_t - r.due_t) * 1e3
                if self.ok(r) and r.first_token_t is not None else st.MISS
                for r in self.rows]

    def tpot_ms(self) -> list:
        out = []
        for r in self.rows:
            v = st.tpot_ms(r.first_token_t, r.finish_t, r.tokens) \
                if self.ok(r) else None
            if v is not None:
                out.append(v)
        return out

    def tokens_in_window(self) -> int:
        """Output tokens emitted inside the window by requests that (in
        the end) finished correctly."""
        return sum(r.tokens if r.tokens_in_window is None
                   else r.tokens_in_window
                   for r in self.rows if self.ok(r))

    def end_to_end(self, name: str) -> Optional[float]:
        """``ttft_p<q>_ms``, ``tpot_p<q>_ms`` (q a whole percentile) and
        ``out_tok_per_s``; ``setup_s`` is the runner's own."""
        m = re.fullmatch(r"(ttft|tpot)_p(\d{1,2})_ms", name)
        if m and m.group(1) == "ttft":
            limit_ms = (self.window_s + self.drain_limit_s) * 1e3
            return st.tail_or_limit(self.ttft_ms(), int(m.group(2)) / 100,
                                    limit_ms)
        if m:
            return st.percentile(self.tpot_ms(), int(m.group(2)) / 100)
        if name == "out_tok_per_s":
            return st.rate(self.tokens_in_window(), self.window_s)
        raise KeyError(f"no arithmetic for end-to-end metric {name!r}")

    def sample_counts(self) -> dict:
        n, n_tpot = len(self.rows), len(self.tpot_ms())
        return {"requests": n, "tpot_samples": n_tpot,
                "beyond_p90_ttft": st.samples_beyond(n, 0.9),
                "beyond_p90_tpot": st.samples_beyond(n_tpot, 0.9)}

    # ----------------------------------------------- occupancy (roofline)

    def occupancy(self, samples: int = 400) -> Optional[list]:
        """The contexts of the sequences decoding at each of ``samples``
        instants of the traced interval, ONE BY ONE (a list of lists;
        instants with none decoding are left out), from the load
        generator's stamps: a request decodes from its first token to
        its finish, its context growing by one token a step (linearly in
        between). A cost that is not linear in a row's context (a window
        layer attends ``min(context, window)``) is taken per row and
        instant and averaged after (``mean_occupancy``)."""
        if self.trace_t0 is None or self.trace_t1 is None:
            return None
        spans = []
        for r in self.rows:
            if r.first_token_t is None or r.tokens < 1:
                continue
            end = r.finish_t if r.finish_t is not None else self.trace_t1
            spans.append((r.first_token_t, max(end, r.first_token_t + 1e-9),
                          len(r.request.prompt_ids), r.tokens))
        out = []
        for i in range(samples):
            t = self.trace_t0 + (i + 0.5) / samples * (
                self.trace_t1 - self.trace_t0)
            active = [(p + toks * (t - a) / (b - a))
                      for a, b, p, toks in spans if a <= t <= b]
            if active:
                out.append(active)
        return out or None

    def mean_occupancy(self, attended=sum, samples: int = 400
                       ) -> Optional[tuple]:
        """Mean active decoding sequences and mean attended context
        tokens over the traced interval: ``attended(contexts)`` of each
        instant's rows (their sum; ``costs.attended_tokens`` where
        layers attend a window)."""
        occ = self.occupancy(samples)
        if occ is None:
            return None
        return (sum(len(a) for a in occ) / len(occ),
                sum(attended(a) for a in occ) / len(occ))


def read_layer_metric(ctx: Context, metric: dict) -> Optional[float]:
    """One per-layer metric through its reader, found by name."""
    reader = importlib.import_module(f"benchmarks.readers.{metric['reader']}")
    return reader.read(ctx, **metric.get("args", {}))
