"""Serving cells: ``ray_tpu.serve.llm.LLMEngine`` driven in this process.

The process that runs this holds the chip, so it can take the profiler's
trace. The entry layer (handle, proxy, router, replica actor) is not in
these cells: see PERF.md, Open questions.

Phases, all but the last two counted as set-up:

1. reach the device; weights on the device from ``--seed`` in ONE jitted
   call, float32 masters as the executor serves them;
2. warm-up on a hand-stepped engine (``auto_step=False``, so that each
   burst meets the scheduler as one batch and reaches exactly the shape it
   is meant to): every prefill and decode shape the cell's traffic can
   reach, named by the traffic file's ``warmup`` and ``engine`` groups;
3. the reference check (what decides ``correct``) on that same engine;
4. a fresh serving-mode engine (``auto_step=True``) over the same weights
   and the same process-wide compiled programs; the clients start;
5. the measured window of ``--seconds``; with ``--trace 1`` the profiler
   runs over a slice of it;
6. drain (open loop) and shut down.
"""
from __future__ import annotations

import gc
import itertools
import threading
import time

import numpy as np

from benchmark import common, stats
from benchmark.common import say


# ------------------------------------------------------------------ set-up


def make_params(spec: dict, cfg, seed: int):
    """The family's own initialiser, in one jitted call from the seed."""
    import jax

    init = common.load_named("reference", spec["config"]["family"]).init_fn()
    key = jax.random.PRNGKey(seed % (2 ** 31))
    return jax.jit(lambda k: init(k, cfg))(key)


def make_engine(spec: dict, cfg, params, *, auto_step: bool):
    from ray_tpu.serve.llm import EngineConfig, LLMEngine

    family = common.load_named("reference", spec["config"]["family"])
    settings = dict(spec["traffic"]["engine"])
    for key in ("length_buckets", "batch_buckets"):
        if settings.get(key) is not None:
            settings[key] = tuple(settings[key])
    return LLMEngine(
        EngineConfig(model=family.ENGINE_MODEL, model_config=cfg, **settings),
        params=params, auto_step=auto_step,
    )


def _step_until_done(engine, streams, limit: int = 100000) -> None:
    for _ in range(limit):
        if all(s.done for s in streams):
            return
        if not engine.step():
            break
    if not all(s.done for s in streams):
        raise RuntimeError("warm-up requests did not finish")


def warm_up(engine, spec: dict, cfg, seed: int) -> None:
    """Every shape the cell's traffic can reach, on a hand-stepped engine.

    Prefill shape (B, S): ``B`` requests of a length in bucket ``S`` and one
    token to produce, submitted together. Decode shape (B, ctx): one request
    whose context lies in bucket ``ctx`` is prefilled alone, then ``B - 1``
    short ones join before the next step, and all decode together for two
    steps. Token ids are random, so no warm-up request shares a prefix with
    another and every prompt takes the monolithic prefill path, as the
    traffic's prompts do."""
    traffic = spec["traffic"]
    buckets = list(traffic["engine"]["length_buckets"])
    longest_prompt = traffic["prompt_len"]["max"]
    rng = np.random.default_rng([seed % (2 ** 31), 7])

    def prompt(n: int) -> list[int]:
        return rng.integers(1, cfg.vocab_size, size=n).tolist()

    def in_bucket(k: int) -> int:
        # a length inside bucket k: above the bucket below, and short
        # enough that three more tokens still fit the bucket
        below = buckets[k - 1] if k else 0
        return max(below + 1, buckets[k] - 8)

    short = in_bucket(0)
    for k in range(len(buckets)):
        if (buckets[k - 1] if k else 0) >= longest_prompt:
            continue  # no prompt of the traffic reaches this prefill shape
        for b in traffic["warmup"]["prefill_batches"]:
            streams = [engine.submit(prompt(in_bucket(k)), max_new_tokens=1)
                       for _ in range(b)]
            _step_until_done(engine, streams)
    for k in range(len(buckets)):
        for b in traffic["warmup"]["decode_batches"]:
            streams = [engine.submit(prompt(in_bucket(k)), max_new_tokens=3)]
            engine.step()  # the long one is prefilled alone
            streams += [engine.submit(prompt(short), max_new_tokens=3)
                        for _ in range(b - 1)]
            _step_until_done(engine, streams)


def reference_check(engine, spec: dict, cfg, seed: int) -> dict:
    """What decides ``correct`` for a serving cell, outside the window.

    A few seeded requests run greedily through the engine (prefill, then
    decoding through the paged cache). The plain float32 reference then
    computes, on the engine's own weights, the logits over prompt + the
    engine's own tokens, and at every ``every``-th generated position the
    reference logit of the engine's token must lie within
    ``tolerance_logit`` of the reference's largest logit (the reason for
    the tolerance is written beside it in the configuration file)."""
    import jax
    import jax.numpy as jnp

    chk = spec["config"]["reference_check"]
    ref = common.load_named("reference", spec["config"]["family"])
    rng = np.random.default_rng([seed % (2 ** 31), 11])
    lens = chk["prompt_tokens"][: chk["requests"]]
    new, every, pad_to = chk["new_tokens"], chk["every"], chk["pad_to"]
    prompts = [rng.integers(1, cfg.vocab_size, size=n).tolist() for n in lens]
    streams = [engine.submit(p, max_new_tokens=new, temperature=0.0)
               for p in prompts]
    _step_until_done(engine, streams)
    outs = [list(s) for s in streams]
    for p, o in zip(prompts, outs):
        if len(o) != new or len(p) + new > pad_to:
            raise RuntimeError(
                f"reference check: a request gave {len(o)} of {new} tokens "
                f"(prompt {len(p)}, pad_to {pad_to})")
    ks = list(range(0, new, every))
    tokens = np.zeros((len(prompts), pad_to), np.int32)
    positions = np.zeros((len(prompts), len(ks)), np.int32)
    picked = np.zeros((len(prompts), len(ks)), np.int32)
    for i, (p, o) in enumerate(zip(prompts, outs)):
        seq = p + o
        tokens[i, : len(seq)] = seq
        for j, k in enumerate(ks):
            # the logits at position len(p) + k - 1 choose generated token k
            positions[i, j] = len(p) + k - 1
            picked[i, j] = o[k]
    logits = jax.jit(lambda pr, t, pos: ref.logits_at(pr, t, pos, cfg))(
        engine.params, jnp.asarray(tokens), jnp.asarray(positions))
    logits = np.asarray(jax.block_until_ready(logits), np.float32)
    top = logits.max(axis=-1)
    own = np.take_along_axis(logits, picked[..., None], axis=-1)[..., 0]
    deficit = top - own
    same = int((logits.argmax(axis=-1) == picked).sum())
    tol = chk["tolerance_logit"]
    ok = bool(np.isfinite(logits).all() and (deficit <= tol).all())
    out = {"checked": int(deficit.size), "same_token": same,
           "max_deficit": float(deficit.max()), "tolerance": tol, "ok": ok}
    say(f"reference check: {out}")
    return out


# ------------------------------------------------------------- the clients


class Clients:
    """The load, from this one process: a record per request, filled at the
    client's side of ``engine.submit`` on one ``time.perf_counter``."""

    def __init__(self, engine, schedule, sampling: dict, vocab_size: int):
        self.engine = engine
        self.schedule = schedule
        self.sampling = sampling
        self.vocab_size = vocab_size
        self.records: list[dict] = []
        self.stop = threading.Event()
        self.threads: list[threading.Thread] = []
        self._next = itertools.count()

    def _submit(self, i: int, due: float | None) -> tuple:
        req = self.schedule.request(i)
        now = time.perf_counter()
        rec = {"index": i, "due": now if due is None else due, "sent": now,
               "tokens": [], "want": req["max_new_tokens"], "error": None,
               "bad_ids": 0, "id": None, "done": None}
        self.records.append(rec)
        try:
            stream = self.engine.submit(
                req["prompt"].tolist(),
                max_new_tokens=req["max_new_tokens"], **self.sampling)
        except Exception as e:  # noqa: BLE001 — a refusal is a result
            rec["error"] = f"refused: {e!r}"
            rec["done"] = time.perf_counter()
            return rec, None
        rec["id"] = stream.request_id
        return rec, stream

    def _consume(self, rec: dict, stream) -> None:
        try:
            for tok in stream:
                rec["tokens"].append(time.perf_counter())
                if not 0 <= tok < self.vocab_size:
                    rec["bad_ids"] += 1
        except Exception as e:  # noqa: BLE001 — a failed stream is a result
            rec["error"] = f"failed: {e!r}"
        rec["done"] = time.perf_counter()

    # closed loop: each client sends its next request when the last ended

    def _closed_client(self, rec: dict, stream) -> None:
        while True:
            if stream is not None:
                self._consume(rec, stream)
            elif not self.stop.is_set():
                time.sleep(0.05)  # refused: do not spin on a full queue
            if self.stop.is_set():
                return
            rec, stream = self._submit(next(self._next), None)

    def start_closed(self, clients: int) -> None:
        # the first request of every client is sent from this thread, in
        # one go: the scheduler then fills its batch by prefill steps alone
        first = [self._submit(next(self._next), None) for _ in range(clients)]
        for rec, stream in first:
            t = threading.Thread(target=self._closed_client,
                                 args=(rec, stream), daemon=True)
            t.start()
            self.threads.append(t)

    # open loop: requests are sent when they are due, whatever came back

    def _open_dispatcher(self, t_origin: float) -> None:
        for i in itertools.count():
            due = t_origin + self.schedule.due(i)
            delay = due - time.perf_counter()
            if delay > 0 and self.stop.wait(delay):
                return
            if self.stop.is_set():
                return
            rec, stream = self._submit(i, due)
            if stream is not None:
                t = threading.Thread(target=self._consume,
                                     args=(rec, stream), daemon=True)
                t.start()
                self.threads.append(t)

    def start_open(self, t_origin: float) -> None:
        t = threading.Thread(target=self._open_dispatcher, args=(t_origin,),
                             daemon=True)
        t.start()
        self.threads.append(t)

    def join(self, timeout: float = 20.0) -> int:
        """Wait for every thread; returns how many are still alive."""
        deadline = time.monotonic() + timeout
        for t in list(self.threads):
            t.join(max(0.0, deadline - time.monotonic()))
        return sum(t.is_alive() for t in self.threads)


# ------------------------------------------------------------ the window


def _sleep_until(t: float) -> None:
    while True:
        left = t - time.perf_counter()
        if left <= 0:
            return
        time.sleep(min(left, 0.25))


def measure(engine, spec: dict, cfg, seed: int, seconds: float,
            trace_dir: str | None, t_process_start: float,
            rate_per_s: float | None = None) -> dict:
    """Clients, ramp, window, drain on a serving-mode engine. Returns the
    raw material: client records, window clocks, counters before and after.
    ``rate_per_s`` overrides the traffic file's rate (``sweep.py`` only)."""
    from ray_tpu._private.compile_cache import enable_compile_cache

    traffic = spec["traffic"]
    if rate_per_s is not None:
        traffic = common.merged(
            traffic, {"arrivals": {"rate_per_s": rate_per_s}})
    gen = common.load_named("generators", traffic["generator"])
    schedule = gen.build(traffic, seed, cfg.vocab_size)
    say(f"drawn distributions (the same for every seed): "
        f"{schedule.describe()}")
    clients = Clients(engine, schedule, traffic.get("sampling", {}),
                      cfg.vocab_size)
    window = traffic["window"]
    open_loop = traffic["arrivals"]["mode"] == "open"
    cache = enable_compile_cache()
    t_origin = time.perf_counter()
    if open_loop:
        clients.start_open(t_origin)
        t0 = t_origin + window["lead_s"]
    else:
        clients.start_closed(traffic["arrivals"]["clients"])
        t0 = t_origin + window["ramp_s"]
    _sleep_until(t0)
    # ---- the window opens: everything before this instant was set-up
    setup_s = time.time() - t_process_start
    wall0 = time.time()
    stats0 = engine.stats()
    programs0 = cache["hits"] + cache["misses"]
    t1 = t0 + seconds
    trace = None
    if trace_dir is not None:
        _sleep_until(t0 + window["trace_after_s"])
        tracing = common.Tracing(trace_dir)
        tracing.start()
        _sleep_until(tracing.t0 + window["trace_s"])
        trace = tracing.stop()
    _sleep_until(t1)
    # ---- the window closes
    wall1 = time.time()
    stats1 = engine.stats()
    programs1 = cache["hits"] + cache["misses"]
    t_seen_until = t1
    if open_loop:
        # arrivals go on, so that the sample's last requests finish under
        # the load they arrived in; an undrained request has failed
        deadline = t1 + window["drain_s"]
        while time.perf_counter() < deadline:
            sample = stats.due_in_window(list(clients.records), t0, t1)
            if all(r["done"] is not None for r in sample):
                break
            time.sleep(0.1)
        t_seen_until = time.perf_counter()
    clients.stop.set()
    records = [dict(r) for r in list(clients.records)]
    dump = engine.debug_dump()
    timelines = {}
    for r in records:
        if r["id"] is not None:
            tl = engine.request_timeline(r["id"])
            if tl is not None:
                timelines[r["id"]] = tl
    memory = common.fullest_chip_memory_stats()
    signatures = sorted(map(str, engine.fns.signatures))
    engine.shutdown()
    alive = clients.join()
    if alive:
        say(f"WARNING: {alive} client threads did not end")
    compiled_in_window = programs1 - programs0
    if compiled_in_window:
        say(f"WARNING: {compiled_in_window} programs were compiled or read "
            f"from the cache INSIDE the window: the warm-up missed a shape")
    return {
        "records": records, "t0": t0, "t1": t1, "wall0": wall0,
        "wall1": wall1, "t_seen_until": t_seen_until, "setup_s": setup_s,
        "stats_before": stats0, "stats_after": stats1,
        "flight": [s for s in dump.get("steps", [])
                   if wall0 <= s.get("ts", 0) < wall1],
        "timelines": timelines, "memory_stats": memory, "trace_run": trace,
        "compiled_in_window": compiled_in_window, "signatures": signatures,
        "open_loop": open_loop, "seconds": seconds,
        "compile_cache": dict(cache),
    }


def per_second(raw: dict) -> list[list]:
    """The window second by second, from the flight recorder's counts:
    ``[second, decode steps, prefill steps, mean and least rows decoded,
    most waiting, highest kv utilization, prompt tokens prefilled]``."""
    rows: dict[int, list] = {}
    for s in raw["flight"]:
        sec = int(s["ts"] - raw["wall0"])
        r = rows.setdefault(sec, [sec, 0, 0, [], 0, 0.0, 0])
        if s.get("kind") == "decode" and s.get("batch"):
            r[1] += 1
            r[3].append(s["batch"])
        elif str(s.get("kind", "")).startswith("prefill"):
            r[2] += 1
            r[6] += s.get("tokens", 0)
        r[4] = max(r[4], s.get("waiting", 0))
        r[5] = max(r[5], s.get("kv_util", 0.0))
    out = []
    for sec in sorted(rows):
        r = rows[sec]
        b = r[3]
        out.append([sec, r[1], r[2], round(sum(b) / len(b), 1) if b else 0,
                    min(b) if b else 0, r[4], r[5], r[6]])
    return out


def summarise(raw: dict) -> dict:
    """End-to-end values and the counts the result line needs."""
    recs, t0, t1 = raw["records"], raw["t0"], raw["t1"]
    seconds = t1 - t0
    out: dict = {"setup_s": raw["setup_s"]}
    info: dict = {}
    if raw["open_loop"]:
        sample = stats.due_in_window(recs, t0, t1)
        failed = [r for r in sample if stats.failed(r)]
        ttft = stats.ttft_ms(sample, raw["t_seen_until"])
        gaps = stats.gaps_ms(sample)
        late = stats.lateness_ms(sample)
        out["ttft_p50_ms"] = stats.percentile(ttft, 0.5)
        out["itl_p50_ms"] = stats.percentile(gaps, 0.5)
        out["itl_mean_ms"] = sum(gaps) / len(gaps)
        info = {
            "ttft_ms": {f"p{int(q * 100)}": stats.percentile(ttft, q)
                        for q in (0.5, 0.75, 0.9, 0.95, 0.99)},
            "ttft_mean_ms": sum(ttft) / len(ttft),
            "itl_ms": {f"p{int(q * 100)}": stats.percentile(gaps, q)
                       for q in (0.5, 0.9, 0.95, 0.99)},
            "requests_due_in_window": len(sample), "gaps": len(gaps),
            "generator_late_p50_ms": stats.percentile(late, 0.5),
            "generator_late_max_ms": max(late),
            "offered_per_s": len(sample) / seconds,
            "tokens_per_s_in_window": stats.rate(
                stats.tokens_in_window(recs, t0, t1), seconds),
            "waiting_at_open": raw["stats_before"]["waiting"],
            "waiting_at_close": raw["stats_after"]["waiting"],
            "running_at_open": raw["stats_before"]["running"],
            "running_at_close": raw["stats_after"]["running"],
        }
        attempted = sample
    else:
        ended = [r for r in recs
                 if r["done"] is not None and t0 <= r["done"] < t1]
        failed = [r for r in ended if stats.failed(r)]
        out["serve_tokens_per_s"] = stats.rate(
            stats.tokens_in_window(recs, t0, t1), seconds)
        good = [r for r in ended if not stats.failed(r)]
        info = {
            "requests_ended_in_window": len(ended),
            "requests_per_s": len(good) / seconds,
            "running_at_open": raw["stats_before"]["running"],
            "running_at_close": raw["stats_after"]["running"],
            "waiting_at_open": raw["stats_before"]["waiting"],
        }
        attempted = ended
    bad_ids = sum(r["bad_ids"] for r in attempted)
    short = sum(1 for r in attempted
                if r["error"] is None and len(r["tokens"]) != r["want"])
    info.update({
        "attempted": len(attempted), "failed": len(failed),
        "bad_ids": bad_ids, "wrong_count": short,
        "compile_signatures": len(raw["signatures"]),
        "compiled_in_window": raw["compiled_in_window"],
        "compile_cache": raw["compile_cache"],
    })
    return {"values": out, "info": info, "attempted": len(attempted),
            "failed": len(failed),
            "streams_ok": bad_ids == 0 and short == 0 and bool(attempted)}


def set_up(spec: dict, seed: int) -> dict:
    """Phases 1-3: device, weights, warm-up and reference check on a
    hand-stepped engine that is then dropped. ``sweep.py`` shares it."""
    from ray_tpu._private.compile_cache import enable_compile_cache

    cache = enable_compile_cache()
    device = common.device_report(spec["cell"]["chips"])
    say(f"device {device}")
    import jax

    cfg = common.model_config(spec["config"])
    t = time.perf_counter()
    params = jax.block_until_ready(make_params(spec, cfg, seed))
    say(f"weights on the device in {time.perf_counter() - t:.1f}s")
    t = time.perf_counter()
    warm = make_engine(spec, cfg, params, auto_step=False)
    warm_up(warm, spec, cfg, seed)
    say(f"warm-up: {warm.fns.num_compiled_shapes} shapes in "
        f"{time.perf_counter() - t:.1f}s; compile cache {dict(cache)}")
    t = time.perf_counter()
    check = reference_check(warm, spec, cfg, seed)
    say(f"reference check took {time.perf_counter() - t:.1f}s")
    warmed = {str(x) for x in warm.fns.signatures}
    warm.shutdown()
    del warm
    gc.collect()
    return {"device": device, "cfg": cfg, "params": params, "check": check,
            "warmed": warmed}


def run(spec: dict, args, t_process_start: float) -> dict:
    up = set_up(spec, args.seed)
    cfg, check = up["cfg"], up["check"]
    engine = make_engine(spec, cfg, up["params"], auto_step=True)
    say(f"executor {engine.executor.describe()}")
    raw = measure(engine, spec, cfg, args.seed, args.seconds, args.trace_dir,
                  t_process_start)
    summary = summarise(raw)
    say("per second [s, decodes, prefills, rows mean, rows min, waiting max,"
        f" kv max, prompt tokens]: {per_second(raw)}")
    missed = sorted(set(raw["signatures"]) - up["warmed"])
    if missed:
        say(f"WARNING: shapes the run reached and the warm-up had not: "
            f"{missed}")
    say(f"run: {summary['info']}")
    ctx = dict(raw)
    ctx.update({
        "config": spec["config"], "traffic": spec["traffic"],
        "model_config": cfg, "end_to_end": summary["values"],
        "reference_check": check, "spans": {},
    })
    return {
        "device": up["device"],
        "correct": bool(check["ok"] and summary["streams_ok"]),
        "attempted": summary["attempted"], "failed": summary["failed"],
        "end_to_end": summary["values"], "info": summary["info"],
        "ctx": ctx,
        "memory_peak_bytes": common.memory_peak_bytes(raw["memory_stats"]),
    }
