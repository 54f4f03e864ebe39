"""DecodeEngine scheduling: share of the window's cold-fill rows that went
into their slot in chunks with a decode step between chunks, where the rest
went through a bucket's one-shot program that every live stream waits for
(`prefill_rows_chunked` / (`prefill_rows_chunked` + `prefill_rows_computed`),
the engine's lifetime counters from `stats()` differenced over the window).
0.0 where no fill was chunked (a model without a chunk program, prompts no
longer than a chunk, a window without fills): the engine counts them, so the
line carries the share. A program whose engine has no such counter (the
parent's) gives nothing to read."""


def read(run):
    c = run.obs.get("counters") or {}
    if "prefill_rows_chunked" not in c:
        return None
    chunked = c["prefill_rows_chunked"]
    rows = chunked + c.get("prefill_rows_computed", 0)
    return 100.0 * chunked / rows if rows else 0.0
