"""chip_smoke.py — the quickest proof that ray_tpu still starts on the chip.

Drives the two main paths once, through the entry points a user calls, at
GPT-2 125M's published widths (``GPTConfig.gpt2_small()``: 12 layers,
d_model 768, 12 heads of 64, d_mlp 3072, vocab 50,304, context 1,024,
bf16; random weights from ``--seed``):

- ``serve``: ``ray_tpu.init()`` + ``serve.run(build_llm_app(...))`` with one
  replica that holds the chip (``num_tpus=1``), a KV pool of 34 x 1,024
  tokens, a few requests through the deployment handle, one through the
  HTTP proxy as SSE and a concurrent burst. Deployed twice — resolved
  attention backend ``pallas`` (compiled kernels), then ``xla`` after the
  first replica's process has exited — and the two sets of streams are
  compared under the device contract of docs/SERVING_LLM.md.
- ``train``: ``JaxTrainer(..., ScalingConfig(num_workers=1, use_tpu=True))``
  whose loop takes 10 AdamW steps (jitted, parameters and optimizer state
  donated) at bs 24 x seq 1,024 with the flash kernels, on a fixed batch.
  The loss must be finite and fall.

``--chips 4`` runs the four-chip path and nothing else: the same engine
with ``tp=4`` through ``ShardedExecutor`` and the single-chip engine it is
compared with, in ONE child process that drives all four chips.

One process per chip at a time: this parent never initialises a JAX
backend; every phase runs in a replica, a train worker or a child, and the
next one starts only after the last one's process has exited.

The last line of stdout is ``{"ok": true, "device": {"platform": "tpu",
"kind": ..., "count": N}}`` with the device as JAX reports it. Any failed
phase, or no TPU, makes the exit code nonzero and prints no such line.

Rehearsal without the chip (on-chip-measurement guide section 2): the
explicit test hook ``CHIP_SMOKE_REHEARSAL=1`` runs the same control flow at
a tiny size on ``JAX_PLATFORMS=cpu`` with the kernels in the Pallas
interpreter (``RAY_TPU_PALLAS_INTERPRET=1``); for ``--chips 4`` add
``XLA_FLAGS=--xla_force_host_platform_device_count=4``. Its last line says
``"rehearsal": true`` and names the CPU, never a device it did not run on.
"""
from __future__ import annotations

import argparse
import dataclasses
import functools
import json
import os
import subprocess
import sys
import threading
import time
import urllib.request

HERE = os.path.dirname(os.path.abspath(__file__))
REHEARSAL = os.environ.get("CHIP_SMOKE_REHEARSAL") == "1"
if REHEARSAL:
    # one pretended chip, so that the replica and the train worker are
    # scheduled and bound to "their chip" exactly as on the real host
    os.environ.setdefault("RT_NUM_TPUS", "1")
# the device contract for backends and mesh shapes (docs/SERVING_LLM.md "On
# the chip"): the share of teacher-forced positions at which the second
# engine picks the first engine's token
MIN_AGREEMENT = 0.9


def say(*parts) -> None:
    print("[chip_smoke]", *parts, flush=True)


def model_config():
    from ray_tpu.models.gpt import GPTConfig

    if REHEARSAL:
        return GPTConfig.tiny()
    return GPTConfig.gpt2_small()


def sizes() -> dict:
    """Request and pool sizes: real on the chip, tiny in rehearsal."""
    if REHEARSAL:
        return dict(num_blocks=64, prompts=(20, 40, 60, 80), sampled=30,
                    http=50, burst=(24, 24, 40, 40), new=8,
                    length_buckets=(32, 64, 128), train_bs=2, train_seq=64)
    return dict(
        # 34 sequences x 1,024 tokens of 16-token blocks (+ block 0, the
        # garbage sink): ~1.3 GB of bf16 K/V across the 12 layers
        num_blocks=34 * 64 + 1,
        prompts=(200, 400, 600, 800), sampled=300, http=500,
        burst=(220, 220, 450, 450), new=48,
        length_buckets=(256, 512, 1024), train_bs=24, train_seq=1024,
    )


def make_prompts(seed: int, vocab: int, lengths) -> list[list[int]]:
    import numpy as np

    rng = np.random.default_rng(seed)
    return [rng.integers(1, vocab, size=n).tolist() for n in lengths]


def wait_exit(pid: int, timeout: float = 60.0) -> float:
    """Block until process ``pid`` has exited (gone, or a zombie: a zombie
    has closed its files and let go of the chip). Returns the wait."""
    t0 = time.monotonic()
    while time.monotonic() - t0 < timeout:
        try:
            with open(f"/proc/{pid}/stat") as f:
                state = f.read().rsplit(")", 1)[1].split()[0]
        except OSError:
            return time.monotonic() - t0
        if state == "Z":
            return time.monotonic() - t0
        time.sleep(0.1)
    raise RuntimeError(f"process {pid} still holds on after {timeout}s")


def check_device(report: dict, what: str) -> None:
    want = "cpu" if REHEARSAL else "tpu"
    if report["platform"] != want:
        raise RuntimeError(
            f"{what} ran on platform {report['platform']!r}, not {want!r}"
        )


def common_prefix(a: list[int], b: list[int]) -> float:
    """Share of the stream up to the first token that differs."""
    n = next((i for i, (x, y) in enumerate(zip(a, b)) if x != y),
             min(len(a), len(b)))
    return n / max(len(a), len(b), 1)


def replay(ask, prompts: dict, ref: dict, own: dict, names: str) -> None:
    """Hold the engine behind ``ask(prompt, max_new)`` to the device
    contract against the reference engine's greedy streams ``ref``.

    Free-running streams of two correct engines drift apart on the chip:
    bf16 rounds differently in the Pallas kernel and in the XLA
    formulation (and under another all-reduce order), a near-tie between
    two logits flips, and from there the streams are different texts. So
    the contract is teacher-forced: at every fourth position k of every
    greedy stream, this engine is given the prompt plus the reference's
    first k tokens and must pick the reference's token k. A broken kernel
    agrees about one time in vocab_size."""
    hits = total = 0
    for key, prompt in prompts.items():
        for k in range(0, len(ref[key]), 4):
            tok = ask(prompt + ref[key][:k], 1)[0]
            hits += tok == ref[key][k]
            total += 1
    share = hits / total
    drift = {k: round(common_prefix(ref[k], own[k]), 3) for k in prompts}
    say(f"agreement {names}: {hits}/{total} teacher-forced positions "
        f"({share:.3f}, contract >= {MIN_AGREEMENT}); free-running common "
        f"prefix share per stream, information only: {drift}")
    if share < MIN_AGREEMENT:
        raise RuntimeError(
            f"{names}: teacher-forced agreement {share:.3f} is under the "
            f"contract's {MIN_AGREEMENT}")


# ------------------------------------------------------------------ serve


def free_port() -> int:
    import socket

    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def serve_phase(backend: str, seed: int, ref: dict | None = None
                ) -> tuple[dict, dict]:
    """Deploy, send the requests (and, given the other deployment's
    streams ``ref``, hold this one to the contract against them), tear
    down, wait for the replica's exit. Returns ({request: tokens}, the
    replica's device report)."""
    import ray_tpu
    from ray_tpu import serve
    from ray_tpu.serve.llm import EngineConfig, build_llm_app

    sz = sizes()
    cfg = model_config()
    t_start = time.monotonic()
    ray_tpu.init()
    try:
        tpus = ray_tpu.cluster_resources().get("TPU", 0)
        say(f"serve[{backend}]: cluster resources TPU={tpus}")
        if tpus < 1:
            raise RuntimeError(
                "the node registered no TPU chip (autodetect_tpu_chips "
                "found none): a num_tpus=1 replica would wait forever"
            )
        port = free_port()
        serve.start(http_options={"port": port})
        app = build_llm_app(
            EngineConfig(
                model="gpt", model_config=cfg, seed=seed, block_size=16,
                num_blocks=sz["num_blocks"], max_batch_size=8,
                length_buckets=sz["length_buckets"],
                attention_backend=backend,
            ),
            ray_actor_options={"num_tpus": 1},
        )
        handle = serve.run(app, name="llm", route_prefix="/llm",
                           timeout_s=900)
        say(f"serve[{backend}]: replica healthy after "
            f"{time.monotonic() - t_start:.1f}s")
        new = sz["new"]
        prompts = make_prompts(seed, cfg.vocab_size, sz["prompts"])
        streams: dict[str, list[int]] = {}

        def ask(payload: dict) -> list[int]:
            return [c["token"] for c in handle.remote(payload)]

        t0 = time.monotonic()
        greedy = {f"greedy{n}": p for n, p in zip(sz["prompts"], prompts)}
        for key, prompt in greedy.items():
            streams[key] = ask({"prompt": prompt, "max_new_tokens": new})
        sampled = make_prompts(seed + 1, cfg.vocab_size, (sz["sampled"],))[0]
        streams["sampled"] = ask({
            "prompt": sampled, "max_new_tokens": new, "temperature": 0.8,
            "top_p": 0.9, "seed": 7})
        http_prompt = make_prompts(seed + 2, cfg.vocab_size, (sz["http"],))[0]
        req = urllib.request.Request(
            f"http://127.0.0.1:{port}/llm",
            data=json.dumps(
                {"prompt": http_prompt, "max_new_tokens": new}).encode(),
            headers={"Content-Type": "application/json",
                     "Accept": "text/event-stream"},
        )
        with urllib.request.urlopen(req, timeout=600) as resp:
            ctype = resp.headers["Content-Type"]
            if not ctype.startswith("text/event-stream"):
                raise RuntimeError(f"proxy answered {ctype!r}, not SSE")
            streams["http_sse"] = [
                json.loads(line[len(b"data: "):])["token"]
                for line in resp if line.startswith(b"data: ")
            ]
        # a concurrent burst: batched prefill and batched decode
        burst = make_prompts(seed + 3, cfg.vocab_size, sz["burst"])
        results: dict[int, list[int]] = {}
        errors: list[BaseException] = []

        def one(i: int) -> None:
            try:
                results[i] = ask({"prompt": burst[i], "max_new_tokens": new})
            except BaseException as e:  # noqa: BLE001 — re-raised below
                errors.append(e)

        threads = [threading.Thread(target=one, args=(i,))
                   for i in range(len(burst))]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        if errors:
            raise errors[0]
        for i in range(len(burst)):
            streams[f"burst{i}"] = results[i]
        wall = time.monotonic() - t0
        for key, toks in streams.items():
            if len(toks) != new or not all(
                    isinstance(t, int) and 0 <= t < cfg.vocab_size
                    for t in toks):
                raise RuntimeError(
                    f"{key}: expected {new} token ids below "
                    f"{cfg.vocab_size}, got {toks}")
        if ref is not None:
            replay(
                lambda prompt, n: ask(
                    {"prompt": prompt, "max_new_tokens": n}),
                greedy, ref, streams, f"{backend} vs the first deployment")
        stats = handle.stats.remote().result(timeout=120)
        report = stats["executor"]
        check_device(report, f"serve[{backend}] replica")
        if report["attention_backend"] != backend:
            raise RuntimeError(
                f"resolved attention backend is "
                f"{report['attention_backend']!r}, asked for {backend!r}")
        sigs = stats["compile_signatures"]
        say(f"serve[{backend}]: {len(streams)} requests answered in "
            f"{wall:.1f}s (compilation included); replica reports "
            f"platform={report['platform']} "
            f"device_kind={report['device_kind']!r} "
            f"attention_backend={report['attention_backend']} "
            f"executor={report['executor']}")
        say(f"serve[{backend}]: {len(sigs)} compile signatures: "
            f"{json.dumps(sigs)}")
        say(f"serve[{backend}]: compile cache {stats['compile_cache']} "
            f"(misses were compiled and written, hits were read back)")
        pid = stats["pid"]
    except BaseException:
        try:
            say(f"serve[{backend}] failed; serve.status(): {serve.status()}")
        except Exception as e:  # noqa: BLE001 — diagnostics only
            say(f"serve[{backend}] failed; no status: {e!r}")
        raise
    finally:
        serve.shutdown()
        ray_tpu.shutdown()
    say(f"serve[{backend}]: replica process {pid} exited "
        f"{wait_exit(pid):.1f}s after shutdown")
    return streams, report


# ------------------------------------------------------------------ train


def train_loop(config: dict) -> None:
    """Runs in the train worker, the one process that holds the chip."""
    import jax
    import jax.numpy as jnp

    import optax

    from ray_tpu import train
    from ray_tpu._private.compile_cache import enable_compile_cache
    from ray_tpu.models.gpt import gpt_init, gpt_loss

    cfg = config["model_config"]
    dev = jax.devices()[0]
    params = gpt_init(jax.random.PRNGKey(0), cfg)
    tx = optax.adamw(3e-4, b1=0.9, b2=0.95, weight_decay=0.1)
    opt_state = tx.init(params)

    @functools.partial(jax.jit, donate_argnums=(0, 1))
    def step(params, opt_state, batch):
        loss, grads = jax.value_and_grad(gpt_loss)(params, batch, cfg)
        updates, opt_state = tx.update(grads, opt_state, params)
        return optax.apply_updates(params, updates), opt_state, loss

    batch = {"tokens": jax.random.randint(
        jax.random.PRNGKey(config["seed"]),
        (config["bs"], config["seq"] + 1), 0, cfg.vocab_size, jnp.int32)}
    for i in range(config["steps"]):
        t0 = time.perf_counter()
        params, opt_state, loss = step(params, opt_state, batch)
        loss = float(jax.block_until_ready(loss))
        train.report({
            "step": i, "loss": loss, "seconds": time.perf_counter() - t0,
            "platform": dev.platform, "device_kind": dev.device_kind,
            "compile_cache": dict(enable_compile_cache()),
        })


def train_phase(seed: int) -> None:
    import math

    import ray_tpu
    from ray_tpu.train import JaxTrainer, RunConfig, ScalingConfig

    sz = sizes()
    # the benchmark's form of the model: the unrolled layer loop (the
    # scanned one keeps 23 GB of stacked residuals at this batch — more
    # than the chip has)
    cfg = dataclasses.replace(
        model_config(), attention="flash", scan_layers=False)
    steps = 10
    ray_tpu.init()
    try:
        result = JaxTrainer(
            train_loop,
            train_loop_config={
                "model_config": cfg, "seed": seed, "steps": steps,
                "bs": sz["train_bs"], "seq": sz["train_seq"]},
            scaling_config=ScalingConfig(num_workers=1, use_tpu=True),
            run_config=RunConfig(
                name="chip_smoke",
                storage_path=os.path.join(HERE, "chiprun_out", "train")),
        ).fit()
    finally:
        ray_tpu.shutdown()
    if result.error:
        raise RuntimeError(f"train worker failed:\n{result.error}")
    hist = result.metrics_history
    if len(hist) != steps:
        raise RuntimeError(f"expected {steps} step reports, got {len(hist)}")
    check_device(hist[0], "train worker")
    losses = [h["loss"] for h in hist]
    say(f"train: platform={hist[0]['platform']} "
        f"device_kind={hist[0]['device_kind']!r} losses "
        f"{[round(x, 4) for x in losses]}")
    if not all(math.isfinite(x) for x in losses):
        raise RuntimeError(f"train loss is not finite: {losses}")
    if not losses[-1] < losses[0]:
        raise RuntimeError(f"train loss did not fall: {losses}")
    warm = [h["seconds"] for h in hist[2:]]
    tokens = sz["train_bs"] * sz["train_seq"]
    say(f"train: first step {hist[0]['seconds']:.1f}s (compilation "
        f"included); steps 3-{steps} at {tokens * len(warm) / sum(warm):.0f} "
        f"tokens/s (information only, each step ends in "
        f"block_until_ready); compile cache {hist[-1]['compile_cache']}")


# -------------------------------------------------------------- four chips


def four_chip_child(seed: int) -> None:
    """The ONE process that drives all four chips: the tp=4 engine through
    ShardedExecutor, then the single-chip engine it is compared with."""
    from ray_tpu._private.compile_cache import enable_compile_cache

    cache = enable_compile_cache()
    import jax

    from ray_tpu.serve.llm import EngineConfig, LLMEngine

    devs = jax.devices()
    report = {"platform": devs[0].platform, "device_kind": devs[0].device_kind}
    check_device(report, "four-chip child")
    if len(devs) < 4:
        raise RuntimeError(f"--chips 4 needs four devices, JAX has {devs}")
    sz = sizes()
    cfg = model_config()
    new = sz["new"]
    prompts = make_prompts(seed, cfg.vocab_size, sz["prompts"])
    greedy = {f"greedy{n}": p for n, p in zip(sz["prompts"], prompts)}
    sampled = make_prompts(seed + 1, cfg.vocab_size, (sz["sampled"],))[0]
    streams: dict[str, dict] = {}
    for name, mesh in (("tp4", {"tp": 4}), ("single", {})):
        t0 = time.monotonic()
        eng = LLMEngine(EngineConfig(
            model="gpt", model_config=cfg, seed=seed, block_size=16,
            num_blocks=sz["num_blocks"], max_batch_size=8,
            length_buckets=sz["length_buckets"],
            attention_backend="pallas", **mesh))
        try:
            out = {key: eng.generate(p, max_new_tokens=new)
                   for key, p in greedy.items()}
            out["sampled"] = eng.generate(
                sampled, max_new_tokens=new, temperature=0.8, top_p=0.9,
                seed=7)
            streams[name] = out
            desc = eng.executor.describe()
            say(f"{name}: {len(out)} requests in "
                f"{time.monotonic() - t0:.1f}s (compilation included); "
                f"executor {desc}; {eng.fns.num_compiled_shapes} compile "
                f"signatures")
            if desc["attention_backend"] != "pallas":
                raise RuntimeError(f"{name} resolved {desc}")
            if name == "tp4":
                check_spread(eng, devs[:4])
            else:
                replay(
                    lambda prompt, n: eng.generate(
                        prompt, max_new_tokens=n),
                    greedy, streams["tp4"], out, "single chip vs tp=4")
        finally:
            eng.shutdown()
    say(f"compile cache {cache}")
    print(json.dumps({"ok": True, "device": {
        "platform": devs[0].platform, "kind": devs[0].device_kind,
        "count": len(devs)}}), flush=True)


def check_spread(eng, devs) -> None:
    """Weights and KV pool are in fact spread over the four devices, not
    all on device 0."""
    import jax

    want = {d.id for d in devs}
    leaves = jax.tree.leaves(eng.executor.params)
    split = [x for x in leaves
             if x.addressable_shards[0].data.shape != x.shape]
    for x in leaves:
        if {d.id for d in x.sharding.device_set} != want:
            raise RuntimeError(
                f"a weight lives on {x.sharding.device_set}, not {want}")
    if not split:
        raise RuntimeError("no weight is partitioned: all are replicated")
    per_dev = {d.id: 0 for d in devs}
    for pool in jax.tree.leaves((eng.cache.k, eng.cache.v)):
        if {s.device.id for s in pool.addressable_shards} != want:
            raise RuntimeError("the KV pool is not on all four devices")
        for s in pool.addressable_shards:
            if s.data.shape[3] * len(devs) != pool.shape[3]:
                raise RuntimeError(
                    f"KV pool shard {s.data.shape} of {pool.shape} is not "
                    f"a quarter of the heads")
            per_dev[s.device.id] += s.data.nbytes
    weights = {d.id: sum(s.data.nbytes for x in leaves
                         for s in x.addressable_shards if s.device == d)
               for d in devs}
    in_use = {d.id: (d.memory_stats() or {}).get("bytes_in_use")
              for d in devs}
    say(f"tp4 residency: {len(split)}/{len(leaves)} weights partitioned; "
        f"weight bytes per device {weights}; KV pool bytes per device "
        f"{per_dev}; bytes_in_use per device {in_use}")


# ------------------------------------------------------------------- main


def preflight() -> dict:
    """What JAX finds, asked in a child so this parent stays off the chip
    (the child exits, and lets go of it, before any phase starts)."""
    out = subprocess.run(
        [sys.executable, "-c",
         "import jax, json; d = jax.devices(); print(json.dumps({"
         "'platform': d[0].platform, 'kind': d[0].device_kind, "
         "'count': len(d)}))"],
        capture_output=True, text=True, timeout=300,
    )
    if out.returncode != 0:
        raise RuntimeError(f"JAX found no device:\n{out.stderr[-2000:]}")
    return json.loads(out.stdout.strip().splitlines()[-1])


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4))
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--four-chip-child", action="store_true",
                    help=argparse.SUPPRESS)
    args = ap.parse_args()
    import ray_tpu  # noqa: F401 — fail here in a directory without the repo

    if args.four_chip_child:
        four_chip_child(args.seed)
        return 0
    t0 = time.monotonic()
    device = preflight()
    say(f"JAX finds {device}")
    if REHEARSAL:
        say("REHEARSAL: tiny model on the CPU, kernels interpreted")
    elif device["platform"] != "tpu":
        say(f"no TPU: JAX found platform {device['platform']!r}")
        return 1
    if args.chips == 4:
        if device["count"] < 4:
            say(f"--chips 4 needs four devices, JAX finds {device['count']}")
            return 1
        child = subprocess.run(
            [sys.executable, os.path.abspath(__file__), "--four-chip-child",
             "--seed", str(args.seed)],
            stdout=subprocess.PIPE, text=True)
        lines = child.stdout.strip().splitlines()
        print("\n".join(lines[:-1]), flush=True)
        if child.returncode != 0:
            print(lines[-1] if lines else "", flush=True)
            say(f"four-chip phase failed (exit {child.returncode})")
            return 1
        last = json.loads(lines[-1])
    else:
        pallas, report = serve_phase("pallas", args.seed)
        serve_phase("xla", args.seed, ref=pallas)
        train_phase(args.seed)
        last = {"ok": True, "device": {
            "platform": report["platform"], "kind": report["device_kind"],
            "count": device["count"]}}
    say(f"all phases passed in {time.monotonic() - t0:.1f}s")
    if REHEARSAL:
        last["rehearsal"] = True
    print(json.dumps(last), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
