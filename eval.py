"""Round-quality evaluation: the retrieval/answer-quality artifact.

Closes the third clause of BASELINE.md's north star ("retrieval nDCG
parity"): runs the full ``tools/eval`` pipeline — synthetic QA ->
answers THROUGH THE LIVE CHAIN SERVER (HTTP SSE) -> deterministic
retrieval metrics (nDCG/hit/MRR) + RAGAS-style LLM-graded metrics +
Likert judge — and writes ``EVAL_r{NN}.json`` at the repo root, the
quality sibling of the driver's ``BENCH_r{NN}.json``.

The reference defines this methodology across four notebooks
(reference: tools/evaluation/01_synthetic_data_generation.ipynb,
02_filling_RAG_outputs_for_Evaluation.ipynb, 03_eval_ragas.ipynb,
04_Human_Like_RAG_Evaluation-AIP.ipynb) but publishes no scores —
parity is measured by re-running the same pipeline here, every round.

Honesty model (mirrors bench.py's ``weights`` field):

- **Retrieval metrics are always meaningful.** The corpus is the repo's
  own documentation, questions are synthesized from specific chunks,
  and the deterministic hash embedder + exact store rank them — nDCG
  measures the splitter/embedder/store/ranking stack, no LLM involved.
- **LLM-graded metrics are only meaningful with real weights.** With
  the default random-init dev model the judge/RAGAS verdicts rarely
  parse; the artifact publishes the scored counts so a reader can see
  exactly how much signal each number carries. Point EVAL_MODEL_PATH
  (or BENCH_MODEL_PATH) at a real checkpoint to light them up.

Usage::

    python eval.py                  # dev stack, writes EVAL_r{NN}.json
    GAIE_ROUND=6 python eval.py     # next round's artifact
    EVAL_MODEL_PATH=/ckpts/llama-2-7b python eval.py   # real weights
"""

from __future__ import annotations

import argparse
import asyncio
import json
import os
import threading
import time

REPO = os.path.dirname(os.path.abspath(__file__))


class LiveChainExample:
    """Example adapter that answers THROUGH the live chain server.

    ``tools.eval.runner`` drives an in-process ``BaseExample``; this
    wrapper keeps that interface but routes ``rag_chain`` over the HTTP
    SSE surface (`POST /generate`), so the published answers cover the
    full serving path — aiohttp, streaming, in-stream error degrade —
    not just the chain object (reference: the eval notebooks likewise
    post to the chain server,
    02_filling_RAG_outputs_for_Evaluation.ipynb). Retrieval contexts and
    gold ids come from the server's own index object (shared
    in-process) — the runner's established gold-labeling seam; the
    HTTP ``/documentSearch`` surface itself is covered by
    tests/test_chains.py, not re-measured here.
    """

    def __init__(self, example, base_url: str):
        self._example = example
        self._base = base_url

    @property
    def index(self):
        return self._example.index

    def rag_chain(self, question: str, num_tokens: int):
        import requests
        with requests.post(
                f"{self._base}/generate",
                json={"question": question, "use_knowledge_base": True,
                      "num_tokens": num_tokens},
                stream=True, timeout=600) as resp:
            resp.raise_for_status()
            parts: list[str] = []
            for chunk in resp.iter_content(chunk_size=None,
                                           decode_unicode=True):
                parts.append(chunk)
        text = "".join(parts)
        if "[error]" in text:
            # the server degrades failures into the stream (reference
            # semantics); scoring the error banner would be fiction
            raise RuntimeError(f"in-stream failure: {text[:200]!r}")
        yield text


def serve_http(example):
    """Boot the chain server on an ephemeral port; return (base_url, stop)."""
    from aiohttp import web

    from generativeaiexamples_tpu.chains.server import create_app

    app = create_app(example)
    loop = asyncio.new_event_loop()
    holder: dict = {}
    started = threading.Event()

    def run():
        asyncio.set_event_loop(loop)

        async def boot():
            runner = web.AppRunner(app)
            await runner.setup()
            site = web.TCPSite(runner, "127.0.0.1", 0)
            await site.start()
            holder["port"] = site._server.sockets[0].getsockname()[1]
        loop.run_until_complete(boot())
        started.set()
        loop.run_forever()

    thread = threading.Thread(target=run, daemon=True)
    thread.start()
    if not started.wait(timeout=60):
        raise RuntimeError("chain server failed to start")

    def stop():
        loop.call_soon_threadsafe(loop.stop)

    return f"http://127.0.0.1:{holder['port']}", stop


def build_stack(args):
    """(example, engine, weights_desc): the canonical QA chatbot over an
    in-process engine + deterministic hash retriever."""
    from generativeaiexamples_tpu.chains.examples.developer_rag import QAChatbot
    from generativeaiexamples_tpu.chains.llm import EngineLLM
    from generativeaiexamples_tpu.serving.model_server import build_services
    from generativeaiexamples_tpu.utils.app_config import AppConfig
    from generativeaiexamples_tpu.utils.configuration import from_dict

    model_path = args.model_path
    model_type = "llama" if model_path else "dev"
    engine, _, model_name = build_services(
        model_type=model_type, model_name=args.model_name,
        model_path=model_path, max_slots=4,
        world_size=args.world_size,
        max_input_length=args.max_input_length,
        max_output_length=256, dtype=args.dtype,
        quantization=args.quantization, with_embedder=False)
    weights = model_path or "random-init"

    cfg = from_dict(AppConfig, {
        "embeddings": {"model_engine": "hash",
                       "dimensions": args.embedding_dim},
        "vector_store": {"name": "exact"},
        "text_splitter": {"chunk_size": args.chunk_size,
                          "chunk_overlap": args.chunk_overlap},
    })
    example = QAChatbot(llm=EngineLLM(engine), config=cfg)
    return example, engine, model_name, weights


def ingest_corpus(example, corpus_dir: str) -> dict:
    exts = (".md", ".txt", ".pdf")
    files = sorted(f for f in os.listdir(corpus_dir)
                   if f.endswith(exts)
                   and os.path.isfile(os.path.join(corpus_dir, f)))
    for name in files:
        example.ingest_docs(os.path.join(corpus_dir, name), name)
    return {"dir": os.path.relpath(corpus_dir, REPO), "files": len(files),
            "chunks": len(example.index._docs)}


def generation_sanity(questions) -> dict:
    """Deterministic answer-stream health, meaningful at any weight
    quality: did every question produce a non-empty, non-error answer
    through the live server?"""
    answers = [q.answer for q in questions]
    non_empty = [a for a in answers if a.strip()]
    return {
        "answers": len(answers),
        "non_empty": len(non_empty),
        "mean_answer_chars": (round(sum(map(len, non_empty))
                                    / len(non_empty), 1)
                              if non_empty else 0),
    }


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(
        description="RAG quality eval against the live chain server; "
                    "writes EVAL_r{NN}.json")
    parser.add_argument("--round", default=os.environ.get("GAIE_ROUND", "05"))
    parser.add_argument("--output", default=None)
    parser.add_argument("--corpus", default=os.path.join(REPO, "docs"))
    parser.add_argument("--model-path", default=os.environ.get(
        "EVAL_MODEL_PATH", os.environ.get("BENCH_MODEL_PATH", "")))
    parser.add_argument("--model-name", default="")
    parser.add_argument("--dtype", default=os.environ.get(
        "EVAL_DTYPE", "bfloat16"))
    parser.add_argument("--quantization", default=os.environ.get(
        "EVAL_QUANT", ""))
    parser.add_argument("--max-input-length", type=int, default=3000)
    parser.add_argument("--world-size", type=int, default=0,
                        help="devices for the engine (0 = all local)")
    parser.add_argument("--embedding-dim", type=int, default=256)
    parser.add_argument("--chunk-size", type=int, default=150)
    parser.add_argument("--chunk-overlap", type=int, default=30)
    parser.add_argument("--top-k", type=int, default=4)
    parser.add_argument("--num-tokens", type=int, default=100)
    parser.add_argument("--max-questions", type=int, default=24)
    parser.add_argument("--max-chunks", type=int, default=24)
    parser.add_argument("--no-artifact", action="store_true",
                        help="print metrics only, write nothing")
    args = parser.parse_args(argv)

    rnd = str(args.round).zfill(2)
    out_path = args.output or os.path.join(REPO, f"EVAL_r{rnd}.json")

    example, engine, model_name, weights = build_stack(args)
    corpus = ingest_corpus(example, args.corpus)
    base_url, stop = serve_http(example)

    from generativeaiexamples_tpu.tools.eval.runner import (EvalConfig,
                                                            run_eval)
    live = LiveChainExample(example, base_url)
    cfg = EvalConfig(top_k=args.top_k, num_tokens=args.num_tokens,
                     pairs_per_chunk=2, max_questions=args.max_questions,
                     max_chunks=args.max_chunks, judge=True, ragas=True)
    try:
        report = run_eval(live, example.llm, cfg)
    finally:
        stop()
        engine.stop()

    artifact = {
        "round": int(rnd),
        "generated_unix": int(time.time()),
        "stack": {
            "llm": model_name,
            "weights": weights,
            "dtype": args.dtype,
            "quantization": args.quantization or None,
            "embedder": f"hash-{args.embedding_dim} (deterministic)",
            "vector_store": "exact",
            "transport": "live chain-server HTTP (streamed /generate)",
        },
        "corpus": corpus,
        "metrics": report.metrics,
        "generation": generation_sanity(report.questions),
        "notes": (
            "retrieval.* (nDCG/hit/MRR vs each question's source chunk) "
            "is deterministic and meaningful on any weights; "
            "faithfulness/context_precision/judge are LLM-graded — on "
            "random-init weights their *_scored counts show how many "
            "verdicts parsed (usually zero). Set EVAL_MODEL_PATH to "
            "score them with a real checkpoint."),
        "questions": [q.to_dict() for q in report.questions],
    }
    if not args.no_artifact:
        with open(out_path, "w") as f:
            json.dump(artifact, f, indent=2)
            f.write("\n")
    summary = {k: artifact["metrics"].get(k) for k in
               ("num_questions", "retrieval", "faithfulness",
                "context_precision", "judge")}
    summary["weights"] = weights
    print(json.dumps(summary))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
