"""The training cell: ``JaxTrainer`` with one worker that holds the chip.

This parent never touches JAX. ``loop`` below is the benchmark's own train
loop; it runs in the worker, makes the weights, warms up, measures the
window, takes the profiler's trace there when asked, and hands everything
back through ``train.report``.

The train step is a copy of ``ray_tpu/benchmarks/gpt_mfu.py
make_train_step`` (AdamW, donated parameters and optimizer state, each
timed step ending in ``block_until_ready``): the yardstick may not move
with the program. The original is listed in PERF.md for a later PR.
"""
from __future__ import annotations

import math
import os
import time

from benchmark import common
from benchmark.common import say


def make_train_step(cfg, family, optimizer: dict, seed: int):
    """``(train_step, params, opt_state)``: parameters from the seed in one
    jitted call; ``train_step(params, opt_state, batch) -> (params,
    opt_state, loss)`` with both states donated."""
    from functools import partial

    import jax
    import optax

    program_loss = family.loss_fn()
    if optimizer["name"] != "adamw":
        raise SystemExit(f"unknown optimizer {optimizer['name']!r}")
    tx = optax.adamw(optimizer["learning_rate"], b1=optimizer["b1"],
                     b2=optimizer["b2"],
                     weight_decay=optimizer["weight_decay"])
    init = family.init_fn()

    @jax.jit
    def make(key):
        params = init(key, cfg)
        return params, tx.init(params)

    params, opt_state = make(jax.random.PRNGKey(seed % (2 ** 31)))

    @partial(jax.jit, donate_argnums=(0, 1))
    def train_step(params, opt_state, batch):
        loss, grads = jax.value_and_grad(program_loss)(params, batch, cfg)
        updates, opt_state = tx.update(grads, opt_state, params)
        return optax.apply_updates(params, updates), opt_state, loss

    return train_step, params, opt_state


def loop(config: dict) -> None:
    """Runs in the train worker, the one process that holds the chip."""
    from ray_tpu._private.compile_cache import enable_compile_cache

    cache = enable_compile_cache()
    import jax
    import jax.numpy as jnp
    import numpy as np

    from ray_tpu import train

    spec, seed, seconds = config["spec"], config["seed"], config["seconds"]
    job = spec["traffic"]
    device = common.device_report(spec["cell"]["chips"])
    family = common.load_named("reference", spec["config"]["family"])
    cfg = common.model_config(spec["config"], job.get("model_overrides"))
    gen = common.load_named("generators", job["generator"])
    batches = gen.build(job, seed, cfg.vocab_size)
    step_fn, params, opt_state = make_train_step(
        cfg, family, job["optimizer"], seed)

    # what decides `correct`, part one: the program's step-0 loss on a few
    # sequences of the first batch against the plain float32 reference
    chk = spec["config"]["reference_check"]
    first = batches.batch(0)
    few = jnp.asarray(first[: chk["loss_sequences"]])
    program_loss = family.loss_fn()
    own = float(jax.jit(lambda p, t: program_loss(p, {"tokens": t}, cfg))(
        params, few))
    ref = float(jax.jit(lambda p, t: family.loss(p, t, cfg))(params, few))
    loss_ok = math.isfinite(own) and abs(own - ref) <= chk["loss_tolerance"]

    def one_step(i: int):
        nonlocal params, opt_state
        batch = {"tokens": jax.device_put(batches.batch(i))}
        params, opt_state, loss = step_fn(params, opt_state, batch)
        return float(jax.block_until_ready(loss))

    losses = [one_step(i) for i in range(job["warmup_steps"])]
    n_warm = len(losses)
    programs0 = cache["hits"] + cache["misses"]
    window = job["window"]
    trace_dir = config["trace_dir"]
    traced = None
    report_s: list[float] = []
    # ---- the window opens: everything before this instant was set-up
    setup_s = time.time() - config["t_process_start"]
    t0 = time.perf_counter()
    ends: list[float] = []
    tracing = None
    while True:
        now = time.perf_counter() - t0
        if now >= seconds:
            break
        if trace_dir and traced is None and tracing is None \
                and now >= window["trace_after_s"]:
            tracing = common.Tracing(trace_dir)
            tracing.start()
            tr_step0 = len(ends)
        i = n_warm + len(ends)
        loss = one_step(i)
        r0 = time.perf_counter()
        train.report({"step": i, "loss": loss})
        r1 = time.perf_counter()
        report_s.append(r1 - r0)
        losses.append(loss)
        ends.append(r1)
        if tracing and r1 - tracing.t0 >= window["trace_s"]:
            traced = dict(tracing.stop(), steps=len(ends) - tr_step0)
            tracing = None
    if tracing:
        traced = dict(tracing.stop(), steps=len(ends) - tr_step0)
    # ---- the window closes with the last completed step
    elapsed = ends[-1] - t0
    programs1 = cache["hits"] + cache["misses"]
    window_losses = losses[n_warm:]
    finite = all(math.isfinite(x) for x in losses)
    fell = (sum(window_losses[-10:]) / len(window_losses[-10:])) < losses[0]
    reduced = None
    if traced:
        from benchmark import trace_reduce

        path = trace_reduce.find_xplane(trace_dir)
        if path:
            reduced = trace_reduce.reduce_file(
                path, stand_in_cpu=common.rehearsal())
    memory = common.fullest_chip_memory_stats()
    train.report({"summary": {
        "device": device, "setup_s": setup_s, "steps": len(ends),
        "elapsed_s": elapsed,
        "tokens_per_step": batches.tokens_per_step,
        "train_tokens_per_s": len(ends) * batches.tokens_per_step / elapsed,
        "loss_first": losses[0], "loss_last10": window_losses[-10:],
        "loss_own": own, "loss_reference": ref, "loss_ok": loss_ok,
        "finite": finite, "fell": fell,
        "report_s": report_s, "trace_run": traced, "trace": reduced,
        "memory_stats": memory,
        "compiled_in_window": programs1 - programs0,
        "compile_cache": dict(cache),
        "n_params": sum(int(np.prod(x.shape))
                        for x in jax.tree.leaves(params)),
    }})


def run(spec: dict, args, t_process_start: float) -> dict:
    # the worker imports this module by name: the checkout on its path
    os.environ["PYTHONPATH"] = os.pathsep.join(
        [common.ROOT] + [p for p in
                         os.environ.get("PYTHONPATH", "").split(os.pathsep)
                         if p])
    import ray_tpu
    from ray_tpu.train import JaxTrainer, RunConfig, ScalingConfig

    if common.rehearsal():
        # one pretended chip, so that the worker is scheduled and bound to
        # "its chip" exactly as on the real host
        os.environ.setdefault("RT_NUM_TPUS", "1")
    ray_tpu.init()
    try:
        chips = ray_tpu.cluster_resources().get("TPU", 0)
        if chips < spec["cell"]["chips"]:
            raise SystemExit(
                f"the node registered {chips} TPU chips, the cell asks for "
                f"{spec['cell']['chips']}: a worker would wait forever")
        result = JaxTrainer(
            loop,
            train_loop_config={
                "spec": spec, "seed": args.seed, "seconds": args.seconds,
                "trace_dir": args.trace_dir,
                "t_process_start": t_process_start},
            scaling_config=ScalingConfig(num_workers=1, use_tpu=True),
            run_config=RunConfig(name="benchmark",
                                 storage_path=os.path.join(args.out_dir, "train")),
        ).fit()
    finally:
        ray_tpu.shutdown()
    if result.error:
        raise SystemExit(f"train worker failed:\n{result.error}")
    s = result.metrics_history[-1]["summary"]
    reports = [h for h in result.metrics_history if "step" in h]
    if len(reports) != s["steps"]:
        raise SystemExit(
            f"the trainer saw {len(reports)} step reports, the loop made "
            f"{s['steps']}")
    if s["compiled_in_window"]:
        say(f"WARNING: {s['compiled_in_window']} programs were compiled or "
            f"read from the cache INSIDE the window")
    info = {k: s[k] for k in (
        "steps", "elapsed_s", "tokens_per_step", "loss_first", "loss_last10",
        "loss_own", "loss_reference", "compiled_in_window", "compile_cache",
        "trace_run", "n_params")}
    say(f"run: {info}")
    values = {"setup_s": s["setup_s"],
              "train_tokens_per_s": s["train_tokens_per_s"]}
    ctx = {
        "config": spec["config"], "traffic": spec["traffic"],
        "end_to_end": values, "trace": s["trace"], "trace_run": s["trace_run"],
        "memory_stats": s["memory_stats"],
        "spans": {"train.report": s["report_s"]},
        "n_params": s["n_params"], "device": s["device"],
        "seconds": s["elapsed_s"],
    }
    return {
        "device": s["device"],
        "correct": bool(s["loss_ok"] and s["finite"] and s["fell"]),
        "attempted": s["steps"], "failed": 0,
        "end_to_end": values, "info": info, "ctx": ctx,
        "memory_peak_bytes": common.memory_peak_bytes(s["memory_stats"]),
    }
