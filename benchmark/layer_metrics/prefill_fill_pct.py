"""Scheduler: how full the window's prefill programs ran: the prompt
tokens they computed over the SLOTS their launches held for them (rows x
row length of every launch, padding rows and padding columns included),
in percent. 100 is a step that holds nothing but real tokens; what is
missing is the device time a cell's prefill share spends on padding.

From ``engine.stats()``, the window's end less its start:
``prefill_tokens_total`` over ``prefill_slots`` (PR 42; every family,
packed steps and steps of a request a row alike). A program that does not
count its slots is read from the window's flight records instead, whose
prefill records have carried ``tokens``, ``bucket_b`` and ``bucket_len``
all along: the same quotient but for the steps at the window's two edges.
Nothing where neither is there or no prefill step ran."""


def read(ctx):
    after = ctx.get("stats_after") or {}
    before = ctx.get("stats_before") or {}
    if "prefill_slots" in after:
        slots = after["prefill_slots"] - before.get("prefill_slots", 0)
        tokens = after["prefill_tokens_total"] - before.get(
            "prefill_tokens_total", 0)
    else:
        steps = [s for s in ctx.get("flight") or ()
                 if str(s.get("kind", "")).startswith("prefill")
                 and "bucket_len" in s]
        slots = sum(s["bucket_b"] * s["bucket_len"] for s in steps)
        tokens = sum(s["tokens"] for s in steps)
    if slots <= 0:
        return None
    return 100.0 * tokens / slots
