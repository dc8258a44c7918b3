"""The benchmark's one entry point.

    python benchmarks/run.py --workload <cell> --seed <n> --seconds <s> \
        --trace <0|1>

One process holds the chip from first to last: weights from the seed,
the logits check against the configuration's plain reference
(``benchmarks/references/``), the engine, the warm-up (all counted as
``setup_s``),
then the measured window, then one JSON object on the LAST line of
standard output: ``correct``, ``attempted``, ``failed``, ``metrics``,
``device`` (and ``breakdown`` with ``--trace 1``). ``--trace 0`` reports
the cell's end-to-end metrics, ``--trace 1`` its per-layer metrics (a
few seconds in the middle of the window are traced). Earlier lines are
JSON too and say what the numbers rest on.

A cell at published widths runs on a TPU or not at all: any other
platform, or fewer chips than the cell asks for, ends the run with a
non-zero exit and no result line.

    python benchmarks/run.py --workload <cell> --sweep-rates 1,2,3 ...

loads once and offers the cell's requests at each rate in turn (the
knee sweep; not a cell, prints one line per rate).
"""

from __future__ import annotations

import time

T_PROCESS_START = time.monotonic()

import argparse      # noqa: E402
import faulthandler  # noqa: E402
import json          # noqa: E402
import os            # noqa: E402
import shutil        # noqa: E402
import sys           # noqa: E402
import traceback     # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(HERE)
sys.path.insert(0, REPO)

from benchmarks.harness import loadgen, spec as spec_mod  # noqa: E402
from benchmarks.harness import stats as st                 # noqa: E402
from benchmarks.harness.context import Context, read_layer_metric  # noqa: E402
from benchmarks.harness.traffic import Generator           # noqa: E402

TIME_LIMIT_S = 1150        # the contract allows a first run 1200 s
TRACE_DIR = os.path.join(REPO, ".bench_trace")


def emit(obj: dict) -> None:
    print(json.dumps(obj), flush=True)


def rounds_summary(rounds: list) -> dict:
    """Host times of the window's rounds (ms): a stall shows here."""
    out: dict = {"n": len(rounds)}
    for field in ("dispatch_ms", "round_ms"):
        vals = [getattr(r, field) for r in rounds if r.done]
        if vals:
            out[field] = {"p50": st.percentile(vals, 0.5),
                          "p90": st.percentile(vals, 0.9),
                          "max": max(vals), "sum": sum(vals)}
    kinds: dict = {}
    for r in rounds:
        kinds[r.kind] = kinds.get(r.kind, 0) + 1
    out["kinds"] = kinds
    return out


class Runner:
    """Builds the system for one cell and runs windows against it."""

    def __init__(self, cell, seed: int):
        self.cell, self.seed = cell, int(seed)
        self.problems: list = []
        self.engine = None
        self.logits: dict = {}      # the logits check's readings
        self.tokens = None          # the engine-token check's

    # ------------------------------------------------------------ set-up

    def sampling(self, max_tokens: int, seed: int):
        from generativeaiexamples_tpu.engine import SamplingParams
        s = self.cell.mix["sampling"]
        return SamplingParams(
            max_tokens=int(max_tokens),
            temperature=float(s.get("temperature", 1.0)),
            top_k=int(s.get("top_k", 0)), top_p=float(s.get("top_p", 1.0)),
            random_seed=int(seed), ignore_eos=True)

    def submit(self, req):
        return self.engine.submit(
            req.prompt_ids, self.sampling(req.max_tokens, req.sampling_seed))

    def generator(self, seed: int, seconds: float, rate=None) -> Generator:
        wl = self.cell.workload
        return Generator(self.cell.mix, self.cell.config["model"]["vocab_size"],
                         seed, rate=rate or wl.get("rate_rps"),
                         seconds=seconds)

    def _try(self, fn, *a, **kw):
        from benchmarks.harness.system import CheckFailed
        try:
            return fn(*a, **kw)
        except CheckFailed as exc:
            self.problems.append(str(exc))
            self.logits = getattr(exc, "readings", None) or self.logits
            return None

    def build(self, seconds: float) -> None:
        from benchmarks.harness import costs, system
        cell, config = self.cell, self.cell.config
        self.device = system.device_info()
        if config.get("platform", "tpu") != "any":
            if self.device["platform"] != "tpu":
                raise SystemExit(
                    f"cell {cell.name} runs at published widths and needs "
                    f"a TPU; JAX reports {self.device['platform']!r}")
            self.peaks = costs.peaks(self.device["kind"])
        else:
            self.peaks = costs.PEAKS.get(self.device["kind"])
        if self.device["count"] < cell.chips:
            raise SystemExit(f"cell {cell.name} asks for {cell.chips} "
                             f"chip(s); JAX sees {self.device['count']}")
        cache_dir = system.setup_jax()
        self.log = system.CompileLog().install()
        t = time.monotonic()
        cfg = system.model_config(config)
        self.encoder = (system.make_encoder_params(self.seed)
                        if config.get("encoder_resident") else None)
        params = system.make_params(cfg, config.get("weight_quant", ""),
                                    self.seed)
        t_weights = time.monotonic() - t
        t = time.monotonic()
        ref = self._try(system.logits_check, params, cfg, config, self.seed)
        t_check = time.monotonic() - t
        t = time.monotonic()
        self.engine = system.build_engine(params, cfg, config, self.seed)
        self.engine.start()
        t_build = time.monotonic() - t
        tok = None
        if ref is not None:
            tok = self._try(system.engine_tokens_check, self.engine, ref,
                            config)
        t = time.monotonic()
        warm = system.warm_up(
            self.engine, cell, self.sampling,
            lambda: self.generator(self.seed + 1, seconds), self.log)
        t_warm = time.monotonic() - t
        if ref is not None:
            self.logits = {k: v for k, v in ref.items() if k != "prompts"}
        self.tokens = tok
        self.report = system.engine_report(self.engine)
        self.programs = set(system.engine_programs(self.engine))
        self._try(system.check_engine, self.report, config)
        emit({"phase": "setup", "cell": cell.name, "seed": self.seed,
              "device": self.device, "compile_cache_dir": cache_dir,
              "weights_s": t_weights, "logits_check_s": t_check,
              "engine_build_s": t_build, "warmup_s": t_warm,
              "programs_built": len(self.log.events),
              "from_cache": self.log.cache_hits,
              "logits_check": self.logits or None,
              "engine_tokens_check": tok, "warmup": warm,
              "engine": self.report})

    # ------------------------------------------------------------ window

    def window(self, seed: int, seconds: float, trace: bool,
               rate=None) -> Context:
        """One measured window (and its drain) against the built system."""
        from benchmarks.harness import trace as tr
        cell, wl, engine = self.cell, self.cell.workload, self.engine
        gen = self.generator(seed, seconds, rate)
        drain_limit = float(wl.get("drain_limit_s", 30.0))
        ctx = Context(cell=cell, rows=[], t0=0.0, t_end=0.0,
                      drain_limit_s=drain_limit, engine_report=self.report,
                      peaks=self.peaks)
        requests = gen.all() if cell.mix["loop"] == "open" else None
        marks = None
        trace_path = os.path.join(TRACE_DIR, cell.name)
        t0 = time.monotonic() + 0.05
        if trace:
            import jax
            shutil.rmtree(trace_path, ignore_errors=True)
            trace_s = min(float(wl.get("trace_seconds", 4.0)), seconds / 2)
            t_on = t0 + (seconds - trace_s) / 2

            def on():
                opts = jax.profiler.ProfileOptions()
                opts.python_tracer_level = 0
                opts.host_tracer_level = 1
                jax.profiler.start_trace(trace_path, profiler_options=opts)
                ctx.trace_t0 = time.monotonic()

            def off():
                ctx.trace_t1 = time.monotonic()
                jax.profiler.stop_trace()

            marks = loadgen.Marks([(t_on, on), (t_on + trace_s, off)]).start()
        time.sleep(max(0.0, t0 - time.monotonic()))
        ctx.t0 = t0
        ctx.stats0 = engine.stats
        if requests is not None:
            ctx.rows = loadgen.run_open(self.submit, requests, t0)
            time.sleep(max(0.0, t0 + seconds - time.monotonic()))
        else:
            ctx.rows = loadgen.run_closed(
                self.submit, gen.next, int(wl["clients"]), t0 + seconds)
        ctx.t_end = time.monotonic()
        ctx.stats1 = engine.stats
        ctx.notes["backlog_at_end"] = sum(1 for r in ctx.rows if not r.done)
        drained = loadgen.drain(ctx.rows, ctx.t_end + drain_limit)
        ctx.notes["drained"] = drained
        ctx.notes["drain_s"] = time.monotonic() - ctx.t_end
        if not drained:
            for r in ctx.rows:
                if not r.done:
                    r.stream.cancel()
        if marks is not None:
            marks.finish()
            ctx.notes["trace_errors"] = marks.errors
        ctx.rounds = [r for r in engine.rounds.records()
                      if ctx.t0 <= r.t_start <= ctx.t_end]
        # a program built while the window's requests drain delays them
        # as one built inside the window does
        ctx.compiles_in_window = self.log.between(ctx.t0, time.monotonic())
        from benchmarks.harness.system import engine_programs
        now = set(engine_programs(engine))
        ctx.notes["programs_first_used_in_window"] = sorted(
            now - self.programs)
        self.programs = now
        if trace and ctx.trace_t1 is not None:
            ctx.trace_rounds = [r for r in engine.rounds.records()
                                if ctx.trace_t0 <= r.t_start <= ctx.trace_t1]
            path = tr.find_xplane(trace_path)
            if path is not None:
                planes = tr.load(path)
                ctx.notes["trace_planes"] = tr.describe(planes)
                ctx.trace = tr.reduce(
                    planes, window_s=ctx.trace_t1 - ctx.trace_t0)
                if ctx.trace is not None:
                    d = ctx.trace.devices[0]
                    ctx.notes["trace_modules"] = {
                        m: [d.module_s[m], d.module_n[m]]
                        for m in sorted(d.module_s, key=d.module_s.get,
                                        reverse=True)[:8]}
        return ctx

    # ----------------------------------------------------------- results

    def outputs_problems(self, ctx: Context) -> list:
        vocab = self.cell.config["model"]["vocab_size"]
        bad = []
        for r in ctx.rows:
            if r.error is not None or r.stream.finish_reason is None:
                continue                    # shed or unfinished: failed
            s = r.stream
            if s.finish_reason == "cancelled" and not ctx.notes["drained"]:
                continue                    # cut at the drain limit
            if s.finish_reason != "length" \
                    or len(s.token_ids) != r.request.max_tokens:
                bad.append(f"request {r.request.uid} ended "
                           f"{s.finish_reason!r} with {len(s.token_ids)} of "
                           f"{r.request.max_tokens} tokens")
            elif not all(0 <= t < vocab for t in s.token_ids):
                bad.append(f"request {r.request.uid}: token id outside the "
                           f"vocabulary")
        return bad[:5]

    def result(self, ctx: Context, trace: bool, setup_s: float) -> dict:
        from benchmarks.harness import system
        self.problems += self.outputs_problems(ctx)
        self._try(system.check_engine, system.engine_report(self.engine),
                  self.cell.config)
        metrics: dict = {}
        if not trace:
            for m in self.cell.end_to_end:
                v = setup_s if m["name"] == "setup_s" \
                    else ctx.end_to_end(m["name"])
                if v is not None:
                    metrics[m["name"]] = {"value": v, "unit": m["unit"]}
        else:
            for m in self.cell.per_layer:
                v = read_layer_metric(ctx, m)
                if v is not None:
                    metrics[m["name"]] = {"value": v, "unit": m["unit"]}
        device = dict(self.device,
                      memory_peak_bytes=system.memory_peak_bytes())
        stats = {k: ctx.stats1.get(k, 0) - ctx.stats0.get(k, 0)
                 for k in ("requests", "prefills", "decode_steps",
                           "tokens_generated", "sched_prefill_tokens",
                           "rejected_full", "sched_budget_recalibrations")}
        fin = [x for x in ctx.ttft_ms() if x != st.MISS]
        tpot = ctx.tpot_ms()
        late = [(r.send_t - r.due_t) * 1e3 for r in ctx.rows]
        emit({"phase": "window", "window_s": ctx.window_s,
              "ttft_ms": {"p50": st.percentile(fin, 0.5),
                          "p75": st.percentile(fin, 0.75),
                          "p90": st.percentile(fin, 0.9),
                          "mean": st.mean(fin), "max": max(fin, default=None)},
              "tpot_ms": {"p50": st.percentile(tpot, 0.5),
                          "p90": st.percentile(tpot, 0.9),
                          "mean": st.mean(tpot)},
              "samples": ctx.sample_counts(), "notes": ctx.notes,
              "budget_tokens": [ctx.stats0["sched_round_budget_tokens"],
                                ctx.stats1["sched_round_budget_tokens"]],
              "counters": stats, "rounds": rounds_summary(ctx.rounds),
              "compiles_in_window": [[n, round(s, 3)] for _, n, s
                                     in ctx.compiles_in_window],
              "lateness_ms": {"p90": st.percentile(late, 0.9),
                              "max": max(late, default=0.0)},
              "failed_examples": [
                  (r.error or r.stream.finish_reason)
                  for r in ctx.failed_rows()[:3]],
              "problems": self.problems})
        out = {"correct": not self.problems, "attempted": len(ctx.rows),
               "failed": len(ctx.failed_rows()), "metrics": metrics,
               "device": device}
        if trace and ctx.trace is not None:
            device["busy_s"] = ctx.trace.busy_s
            device["window_s"] = ctx.trace.window_s
            out["breakdown"] = ctx.trace.breakdown()
        return out

    def compared(self) -> list:
        """Each number ``correct`` compared beside its limit, one line
        each: the last lines of a run's standard error."""
        lc = self.cell.config["logits_check"]
        g = self.logits
        ref = f"references/{self.cell.config['reference']}.py"
        lines = [f"logits_check vs {ref}: {what}_median_rel_err="
                 f"{g.get(what + '_median_rel_err')} "
                 f"limit={lc['median_tolerance']}"
                 for what in ("prefill", "decode")]
        lines.append(f"logits_check: share_over_{lc['tolerance']}="
                     f"{g.get('share_over_tolerance')} (max_rel_err="
                     f"{g.get('max_rel_err')}) limit={lc['max_share_over']}")
        t = self.tokens or {}
        lines.append(f"engine_tokens: within_{lc['tolerance']}="
                     f"{t.get('within_tolerance')} of {t.get('compared')} "
                     f"(worst_gap={t.get('worst_gap')}) "
                     f"limit>={lc['min_token_agreement']} of compared")
        return lines + [f"problem: {p}" for p in self.problems]

    def close(self) -> None:
        if self.engine is not None:
            self.engine.stop()


def sweep(runner: Runner, rates: list, seed: int, seconds: float) -> None:
    """The knee sweep: each rate in turn against one built system; the
    knee is the highest rate whose backlog does not grow and whose
    ttft_p90 stays finite (read off the printed lines). Every rate
    offers the SAME requests — the cell's own layout for ``seconds`` —
    over a window of count / rate seconds, so that a rate differs from
    the cell in nothing but the gaps (and meets no program the warm-up
    did not build). The i-th window runs under ``seed + i``: a rate
    given twice shows how far two windows of one rate differ."""
    count = round(runner.cell.workload["rate_rps"] * seconds)
    lines = []
    for i, rate in enumerate(rates):
        ctx = runner.window(seed + i, count / rate, trace=False, rate=rate)
        ttft = ctx.ttft_ms()
        half = len(ttft) // 2
        line = {
            "sweep_rate_rps": rate, "window_s": ctx.window_s,
            "offered": len(ctx.rows),
            "failed": len(ctx.failed_rows()),
            "backlog_at_end": ctx.notes["backlog_at_end"],
            "drain_s": ctx.notes["drain_s"],
            "ttft_p50_ms": ctx.end_to_end("ttft_p50_ms"),
            "ttft_p90_ms": ctx.end_to_end("ttft_p90_ms"),
            "ttft_p50_first_half_ms": st.percentile(
                [x for x in ttft[:half] if x != st.MISS], 0.5),
            "ttft_p50_second_half_ms": st.percentile(
                [x for x in ttft[half:] if x != st.MISS], 0.5),
            "tpot_p50_ms": ctx.end_to_end("tpot_p50_ms"),
            "tpot_p90_ms": ctx.end_to_end("tpot_p90_ms"),
            "out_tok_per_s": ctx.end_to_end("out_tok_per_s"),
            "budget_tokens": ctx.stats1["sched_round_budget_tokens"],
            "compiles_in_window": len(ctx.compiles_in_window),
            "programs_first_used": ctx.notes[
                "programs_first_used_in_window"],
            "rounds": rounds_summary(ctx.rounds)["kinds"],
            "device": runner.device["kind"]}
        emit(line)
        lines.append(line)
    out_dir = os.path.join(REPO, "chiprun_out")
    os.makedirs(out_dir, exist_ok=True)
    with open(os.path.join(out_dir, f"sweep_{runner.cell.name}.json"),
              "w") as f:
        json.dump({"cell": runner.cell.name, "seed": seed,
                   "seconds": seconds, "rates": lines}, f, indent=1)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, default=None)
    ap.add_argument("--trace", type=int, default=0, choices=(0, 1))
    ap.add_argument("--sweep-rates", default="")
    ap.add_argument("--benchmark-json", default=None,
                    help="another BENCHMARK.json (the tests' rehearsal)")
    ap.add_argument("--data", default=None,
                    help="another data directory (the tests' rehearsal)")
    args = ap.parse_args(argv)

    faulthandler.dump_traceback_later(TIME_LIMIT_S, exit=True)
    spec = spec_mod.Spec(args.benchmark_json, args.data)
    cell = spec.cell(args.workload)
    seconds = float(args.seconds if args.seconds is not None
                    else spec.doc["run_seconds"])
    runner = Runner(cell, args.seed)
    try:
        runner.build(seconds)
        if args.sweep_rates:
            sweep(runner, [float(r) for r in args.sweep_rates.split(",")],
                  args.seed, seconds)
            return 0
        ctx = runner.window(args.seed, seconds, bool(args.trace))
        setup_s = ctx.t0 - T_PROCESS_START
        result = runner.result(ctx, bool(args.trace), setup_s)
    except SystemExit as exc:
        print(exc, file=sys.stderr)
        return 2
    except BaseException:  # noqa: BLE001 — reported, then non-zero exit
        traceback.print_exc()
        return 1
    finally:
        runner.close()
        faulthandler.cancel_dump_traceback_later()
    emit(result)
    print("\n".join(runner.compared()), file=sys.stderr, flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
