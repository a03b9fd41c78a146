"""What a launch moves host -> device, and how (executor.py ``_run``).

A step's numpy staging arrays ride the jitted call itself, and the grammar
allow-mask of a batch with no constrained row is an all-ones array that
RESTS on the device (``ModelExecutor.ones_mask``). Neither may change a
stream: the same values reach the same programs. Held here: streams are
the bytes of an engine that fills and moves an all-ones mask every launch
(what the engine did before), the resident array is never written, a
staging buffer is free for reuse exactly when ``_scratch_buf`` says it is,
and no program is compiled a second time because an argument is a host
array in one launch and a device array in another.
"""
from __future__ import annotations

import dataclasses

import numpy as np
import pytest

EOS = 0
REGEX = {"type": "regex", "pattern": r"(yes|no|maybe)"}


def _engine(**kw):
    import jax.numpy as jnp

    from ray_tpu.models.llama import LlamaConfig
    from ray_tpu.serve.llm import EngineConfig, LLMEngine

    cfg = dataclasses.replace(LlamaConfig.tiny(), dtype=jnp.float32,
                              attention="xla")
    kw.setdefault("block_size", 8)
    kw.setdefault("num_blocks", 64)
    return LLMEngine(
        EngineConfig(model="llama", model_config=cfg, eos_id=EOS, **kw),
        auto_step=False)


def _drain(eng, streams, after_step=None, steps=800):
    for _ in range(steps):
        if all(s.done for s in streams):
            break
        eng.step()
        if after_step is not None:
            after_step()
    while eng.step():  # reconcile what is still in flight
        pass
    assert all(s.done for s in streams)


def _mixed(eng) -> list:
    """Greedy, sampled and constrained rows in one batch (seeds uint32,
    temperatures float32); the short constrained stream finishes first,
    and a row joins after it has."""
    return [
        eng.submit([4, 5, 6], max_new_tokens=12, temperature=0.7, seed=3),
        eng.submit([1, 2, 3], max_new_tokens=4, structured=REGEX),
        eng.submit([9, 8, 7, 6, 5, 4, 3, 2, 1, 9, 8, 7], max_new_tokens=20),
        eng.submit([1, 2, 1, 2, 1, 2, 1, 2], max_new_tokens=14, top_k=5,
                   temperature=1.1, seed=2**32 - 1),
    ]


@pytest.mark.timeout(300)
@pytest.mark.parametrize(
    "kw", [{}, {"speculative_k": 3}, {"tp": 2}], ids=["plain", "verify",
                                                       "tp2"])
def test_resident_mask_streams_are_the_staged_masks(jax_cpu, kw):
    """A constrained and unconstrained rows in one batch, then the
    constrained one finishes: every stream is byte for byte that of an
    engine which stages an all-ones mask from the host every launch, the
    mask is moved by the launches that hold the constrained row and by no
    launch after it finished, and the resident masks are still all ones."""

    def run(resident: bool):
        eng = _engine(**kw)
        if not resident:
            eng.executor.ones_mask = lambda shape: np.full(
                shape, 0xFFFFFFFF, np.uint32)
        streams = _mixed(eng)
        moved_when_done = []

        def note():
            if streams[1].done and not moved_when_done:
                moved_when_done.append(eng.executor.stage_masks)

        for _ in range(5):
            eng.step()
            note()
        streams.append(eng.submit([7, 7, 7], max_new_tokens=9))
        _drain(eng, streams, after_step=note)
        st = eng.stats()
        masks = {shape: np.asarray(m)
                 for shape, m in eng.executor._ones_masks.items()}
        eng.shutdown()
        return [list(s) for s in streams], st, moved_when_done[0], masks

    staged, st_staged, _, no_masks = run(resident=False)
    streams, st, moved_when_done, masks = run(resident=True)
    assert streams == staged
    assert not no_masks and masks
    for shape, mask in masks.items():
        assert mask.shape == shape and mask.dtype == np.uint32
        assert (mask == 0xFFFFFFFF).all()
    launches = st["decode_steps"] + st["prefill_steps"] + st["spec_steps"]
    assert st_staged["host"]["stage_masks"] == launches
    assert 0 < st["host"]["stage_masks"] == moved_when_done < launches
    # the same programs, whichever way the mask came
    assert st["num_compiled_shapes"] == st_staged["num_compiled_shapes"]


@pytest.mark.timeout(300)
def test_a_free_staging_buffer_is_free(jax_cpu):
    """``_scratch_buf``'s contract with host arrays handed to the jitted
    call as they are (which the CPU backend may alias for the call): the
    buffer a slot hands out NEXT is the partner of the one the launch
    just made read, and the launch that read it has been synced, so
    garbage written into it right after every launch changes no id, of
    the launch in flight or of any later one (which must overwrite every
    element it uses)."""

    def run(scribble: bool):
        eng = _engine(prefill_chunk_tokens=8)

        def garbage():
            for slot in eng._scratch.values():
                free = slot[slot[2] ^ 1]
                free.view(np.uint8)[...] = 0x7F

        streams = _mixed(eng)
        for _ in range(4):
            eng.step()
            if scribble:
                garbage()
        streams.append(eng.submit([7, 7, 7], max_new_tokens=9))
        _drain(eng, streams, after_step=garbage if scribble else None)
        eng.shutdown()
        return [list(s) for s in streams]

    assert run(scribble=True) == run(scribble=False)


@pytest.mark.timeout(300)
def test_traffic_after_a_warm_up_compiles_nothing(jax_cpu):
    """An argument is a numpy array in one launch and a device array in
    another (a step's ids: staged cold, the step in flight's otherwise;
    the mask: resident, or staged under a grammar). Neither is part of a
    program: after a warm-up over the step shapes, traffic over the same
    shapes in another order, a constrained request among it, compiles no
    step program and no id gather again."""
    from jax._src import monitoring

    from ray_tpu.serve.llm import executor

    compiled = []

    def listener(name, seconds, **kw):
        if name.endswith("backend_compile_duration"):
            compiled.append(name)

    def run(order, structured=None):
        eng = _engine(block_size=16, max_batch_size=4)
        streams = []
        for n_rows, new in order:
            streams += [eng.submit([i + 1, 2, 3], max_new_tokens=new + i,
                                   temperature=0.5 * i, seed=i)
                        for i in range(n_rows)]
            for _ in range(2):
                eng.step()
        if structured:
            streams.append(eng.submit([1, 2, 3], max_new_tokens=8,
                                      structured=structured))
        _drain(eng, streams)
        st = eng.stats()
        eng.shutdown()
        return st

    warm = run([(1, 3), (2, 3), (4, 3)])  # prefill and decode rows 1, 2, 4
    gathers = executor._feed_ids._cache_size()
    monitoring.register_event_duration_secs_listener(listener)
    try:
        st = run([(2, 5), (1, 2), (1, 9), (2, 4)], structured=REGEX)
    finally:
        monitoring.unregister_event_duration_listener(listener)
    assert st["decode_steps_remapped"] > 0 and st["host"]["stage_masks"] > 0
    assert compiled == []
    assert executor._feed_ids._cache_size() == gathers
    assert st["num_compiled_shapes"] == warm["num_compiled_shapes"]
