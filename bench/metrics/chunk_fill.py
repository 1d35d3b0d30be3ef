"""Scheduler: useful token rows (prompt tokens and riding decode rows) over
the rows computed by chunk-shaped calls (slots x prefill_chunk each), %."""


def read(run):
    steps = [s for s in run.window.steps if s.kind == "chunk"]
    if not steps:
        return None
    useful = sum(s.prompt_rows + s.decode_rows for s in steps)
    return 100.0 * useful / (len(steps) * run.slots * run.chunk)
