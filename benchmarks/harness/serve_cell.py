"""Drives one serving cell: the system under test in this process (it holds
the chip), the load generator in a process of its own.

Path under test, all through the program's normal entry points:
`ray_tpu.init` -> `serve.run(<the family's app>)` (for the Llama family
`build_openai_app(PagedLLMConfig(...))`) -> `serve.start_http_proxy` -> HTTP
/v1/completions with `stream: true` -> router -> replica -> the engine. Set-up, in order: weights from the
seed (one jitted call), the app, the comparison with the reference, one
request per prefill bucket the traffic will use, then the generator's fill.
The window opens when every fill request has its first token.
"""

from __future__ import annotations

import json
import os
import shutil
import subprocess
import sys
import threading
import time
import urllib.request

from benchmarks.harness import device, engine_records, xplane
from benchmarks.harness.engine_tap import EngineTap
from benchmarks.harness.measure import Measurement, log
from benchmarks.harness.spec import ROOT
from benchmarks.harness.tokenizer import IdTokenizer

LOADGEN = os.path.join(os.path.dirname(os.path.abspath(__file__)), "loadgen.py")
# ids of requests the harness sends itself sit above the generator's
CHECK_ID0, WARM_ID0 = 30000, 30100


def _post(url: str, body: dict, timeout: float = 900.0) -> dict:
    req = urllib.request.Request(url, data=json.dumps(body).encode(),
                                 headers={"Content-Type": "application/json"})
    with urllib.request.urlopen(req, timeout=timeout) as resp:
        return json.loads(resp.read())


def _prompt(rid: int, n: int, vocab: int, seed: int) -> list[int]:
    import random

    rng = random.Random(seed * 7919 + rid)
    return [rid % vocab] + [rng.randrange(vocab) for _ in range(n - 1)]


def sampled_row(logits, prompt_len: int):
    """The row of a prefill's logits that the engine samples the first token
    from, whatever shape `_prefill` hands back: row `prompt_len - 1` of a
    `[bucket, vocab]` result, or the only row of one already cut to the last
    live position (`[vocab]` or `[1, vocab]`)."""
    if logits.ndim == 1:
        return logits
    return logits[0] if logits.shape[0] == 1 else logits[prompt_len - 1]


def check_against_reference(tap: EngineTap, url: str, cell, seed: int) -> dict:
    """Prefill then decode through the engine, at the cell's widths, against
    the float32 reference: `sequences` prompts of `prompt_tokens` tokens and
    `new_tokens` generated ones each, one at a time so that the only live
    slot is the sequence. The reference is fed the prompt and the engine's
    own tokens and must give the logits the engine sampled from.

    Tolerance (configuration file, `check`): the engine rounds to bfloat16
    (relative 2^-8) after each of ~7 matrix products in each layer, and the
    errors add like a random walk over layers x products, so the logits are
    off by about sqrt(7 * layers) * 2^-9 of their root-mean-square size:
    0.02 rms at 16 layers. `rel_rms` bounds rms(error) / rms(logit) at three
    times that, and `rel_max` bounds the largest error against the largest
    logit. An 8-bit product (relative 2^-4 or worse) is 16 times off and
    fails; so does a wrong mask, rotation or page, which moves logits by
    their own size."""
    import numpy as np

    chk, m = cell.config["check"], cell.config["model"]
    engine = tap.engine
    tap.capture_logits()
    worst = {"rel_rms": 0.0, "rel_max": 0.0}
    try:
        for k in range(chk["sequences"]):
            del tap.captured[:]
            prompt = _prompt(CHECK_ID0 + k, chk["prompt_tokens"], m["vocab_size"], seed)
            out = _post(url, {"prompt": " ".join(map(str, prompt)),
                              "max_tokens": chk["new_tokens"]})
            new = [int(t) for t in out["choices"][0]["text"].split()]
            if len(new) != chk["new_tokens"]:
                raise RuntimeError(f"asked {chk['new_tokens']} tokens, got {len(new)}")
            rows = []
            for kind, inputs, logits in tap.captured:
                if kind == "prefill":
                    rows.append(sampled_row(logits, len(prompt)))
                else:
                    rows.append(logits[int(inputs[0])])  # the one live slot
            got = np.stack(rows)                       # [new_tokens, vocab]
            seq = prompt + new[:-1]
            want = np.asarray(cell.reference.logits(engine.params, seq, m))
            want = want[len(prompt) - 1:]
            err = got - want
            worst["rel_rms"] = max(worst["rel_rms"], float(
                np.sqrt(np.mean(err ** 2)) / np.sqrt(np.mean(want ** 2))))
            worst["rel_max"] = max(worst["rel_max"], float(
                np.abs(err).max() / np.abs(want).max()))
    finally:
        tap.unwrap()
        del tap.captured[:]
    worst["ok"] = (worst["rel_rms"] <= chk["rel_rms"]
                   and worst["rel_max"] <= chk["rel_max"])
    return worst


def warm_buckets(cell, url: str, plan: dict, vocab: int, seed: int) -> list:
    """One prefill-only request in each prefill bucket the plan will use (the
    decode step and the 128 bucket ran in the check). Returns the buckets."""
    lens = [r["prompt_len"] for key in ("fill", "window", "sequence")
            for r in plan.get(key, ())]
    buckets = list(cell.config["engine"]["prefill_buckets"]) + [
        cell.config["model"]["max_position_embeddings"]]
    by_bucket: dict = {}
    for n in lens:
        b = next(b for b in buckets if n <= b)
        by_bucket[b] = max(by_bucket.get(b, 0), n)
    for k, (b, n) in enumerate(sorted(by_bucket.items())):
        prompt = _prompt(WARM_ID0 + k, n, vocab, seed)
        _post(url, {"prompt": " ".join(map(str, prompt)), "max_tokens": 1})
    return sorted(by_bucket)


def _reduce_records(done: dict, traffic: dict, ms: Measurement) -> None:
    """Client-side samples of the window, from the generator's records."""
    lo, hi = done["t_open"], done["t_close"]
    min_tok = int(traffic.get("min_tokens_for_tpot", 8))
    tpot, gaps, ttft, late = [], [], [], []
    served_tokens = 0
    for r in done["records"]:
        inside = [t for t in r["tokens"] if lo <= t <= hi]
        gaps += [b - a for a, b in zip(inside, inside[1:])]
        if len(inside) >= min_tok:
            tpot.append((inside[-1] - inside[0]) / (len(inside) - 1))
        if not r["fill"]:
            ms.attempted += 1
            ms.failed += 1 if r["error"] else 0
            if r["due"] is not None:
                late.append(r["sent"] - r["due"])
                if r["tokens"] and r["tokens"][0] <= hi:
                    ttft.append(r["tokens"][0] - r["due"])
        elif r["error"]:
            ms.failed += 1
        if r["done"] is not None and lo <= r["done"] <= hi and not r["error"]:
            served_tokens += r["prompt_len"] + len(r["tokens"])
    ms.series.update(tpot_s=tpot, gap_s=gaps, ttft_s=ttft, gen_late_s=late)
    ms.counters["served_tok_s"] = served_tokens / (hi - lo)
    ms.counters["window_tokens"] = sum(
        1 for r in done["records"] for t in r["tokens"] if lo <= t <= hi)
    ms.notes.update(requests_with_tpot=len(tpot), gaps=len(gaps),
                    first_tokens=len(ttft))


def run(cell, seed: int, seconds: float, trace: bool, t_start: float,
        dev: dict, peaks: dict) -> Measurement:
    import jax

    import ray_tpu
    from ray_tpu import serve

    ms = Measurement(config=cell.config, traffic=cell.traffic, peaks=peaks,
                     family=cell.family)
    m, eng, fam = cell.config["model"], cell.config["engine"], cell.family
    serve_app = cell.family_entry("serve_app")
    tap = EngineTap(lambda model_config: fam.seeded_params(model_config, seed))
    plan = cell.kind.plan(cell.traffic, seed, seconds, eng["max_batch_size"])
    gen = None
    log("imports done, plan made")
    trace_dir = os.path.join(ROOT, ".bench_tmp", f"trace-{cell.name}")
    try:
        ray_tpu.init()
        app, engine_cls = serve_app(fam.model_config(m), m, eng, IdTokenizer())
        with tap.constructing(engine_cls):
            handle = serve.run(app, route_prefix="/v1")
            stats = ray_tpu.get(handle.stats.remote())  # the replica is up
        proxy = serve.start_http_proxy(port=0)
        log("app up: runtime, weights from the seed, KV pool, proxy")
        engine = tap.engine
        if engine is None:
            raise RuntimeError(f"the app built no {engine_cls.__name__} in this process")
        if stats["platform"] != dev["platform"]:
            raise RuntimeError(f"the engine runs on {stats['platform']!r}, "
                               f"jax's backend is {dev['platform']!r}")
        url = f"http://127.0.0.1:{proxy.port}/v1/completions"
        check = check_against_reference(tap, url, cell, seed)
        ms.correct = check["ok"]
        ms.notes["check"] = check
        log(f"checked against the reference: {check}")
        ms.notes["buckets"] = warm_buckets(cell, url, plan, m["vocab_size"], seed)
        log(f"warmed prefill buckets {ms.notes['buckets']}")
        plan.update(url=url, vocab=m["vocab_size"], seed=seed, seconds=seconds)
        env = {k: v for k, v in os.environ.items() if not k.startswith(("JAX_", "XLA_", "TPU_"))}
        gen = subprocess.Popen([sys.executable, LOADGEN], stdin=subprocess.PIPE,
                               stdout=subprocess.PIPE, env=env, text=True)
        gen.stdin.write(json.dumps(plan))
        gen.stdin.close()
        opened = json.loads(gen.stdout.readline())
        t_open = opened["t_open"]
        ms.counters["setup_s"] = t_open - t_start
        log(f"window opens: {len(plan['fill'])} fill requests have a first token")
        time.sleep(max(0.0, t_open - time.monotonic()))
        at_open = engine.stats()
        ring_mark = engine_records.mark()
        traced = None
        if trace:
            tr = cell.traffic.get("trace", {})
            start = min(float(tr.get("start_s", 2.0)), max(seconds - 1.0, 0.0))
            length = min(float(tr.get("seconds", 3.0)), max(seconds - start, 0.5))
            time.sleep(max(0.0, t_open + start - time.monotonic()))
            shutil.rmtree(trace_dir, ignore_errors=True)
            jax.profiler.start_trace(trace_dir)
            t_a = time.monotonic()
            time.sleep(length)
            t_b = time.monotonic()
            jax.profiler.stop_trace()
            traced = (t_a, t_b)
        time.sleep(max(0.0, t_open + seconds - time.monotonic()))
        at_close = engine.stats()
        done = json.loads(gen.stdout.readline())
        gen.wait(60)
        log("window closed")
        ms.notes.update(pending_open=at_open["pending"], pending_close=at_close["pending"],
                        active_open=at_open["active_slots"],
                        active_close=at_close["active_slots"],
                        fill_requests=len(plan["fill"]),
                        fill_errors=opened.get("fill_errors", 0))
        _reduce_records(done, cell.traffic, ms)
        if trace:
            # engine-side samples of the window, from the engine's own records
            records = engine_records.since(ring_mark)
            series, counters = engine_records.reduce(
                records, done["t_open"], done["t_close"], done["records"], traced)
            ms.series.update(series)
            ms.counters.update(counters)
            ms.notes["engine_records"] = len(records)
            ms.trace = xplane.reduce_trace_dir(trace_dir)
        ms.counters["memory_peak_bytes"] = device.memory_peak_bytes()
    finally:
        if gen is not None and gen.poll() is None:
            gen.kill()
            gen.wait()
        tap.unwrap()
        if tap.engine is not None:
            # requests cut by the window's close would otherwise be decoded to
            # their end before the app goes down. The engine's own shutdown
            # ends the live slots and, since PR 24, the queued requests too;
            # the replica repeats it harmlessly
            tap.engine.shutdown()
        serve.shutdown()
        ray_tpu.shutdown()
        for t in threading.enumerate():  # an engine thread left inside a
            if t.name.endswith("LLMEngine"):  # jitted call aborts the exit
                t.join(60)
        shutil.rmtree(trace_dir, ignore_errors=True)
        log("app and runtime down")
    return ms
