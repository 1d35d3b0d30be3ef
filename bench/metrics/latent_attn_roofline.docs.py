"""Latent attention: the roofline's least time of the absorbed MLA
attention over each row's live context (bfloat16; the family's
``attn_ops_per_position`` per query row, layer and live position, and its
``latent_bytes_per_position`` read once per live position by a decode row,
at least once per slot's chunk by a chunk's rows) over the device time of
the operations scoped ``attn`` in the traced steps' programs, %.  The step
scores every position of the stripe, so the share shows what the dead
positions cost."""

from bench import counts, engine_trace

CACHE_BYTES = {"bfloat16": 2, "float32": 4, "int8": 1}


def read(run):
    fam = run.family
    per_pos = getattr(fam, "attn_ops_per_position", None)
    rec = engine_trace.record(run)
    steps = run.traced_steps()
    if per_pos is None or rec is None or not steps:
        return None
    attn = {p[0]: p[3].get("attn", 0) for p in rec["programs"]}
    layers = run.model["n_layers"]
    nb = fam.latent_bytes_per_position(
        run.model, CACHE_BYTES[run.cell.engine["cache_dtype"]])
    least = ns = 0.0
    for s, (start, _), _ in steps:
        if not attn.get(start):
            continue
        ns += attn[start]
        reads = s.context_sum if s.kind == "decode" \
            else s.context_sum / run.chunk
        least += counts.least_time(
            layers * per_pos(run.model) * s.context_sum,
            layers * nb * reads, run.peaks["bf16_flops_per_s"],
            run.peaks["hbm_bytes_per_s"])[0]
    return 100.0 * least / (ns / 1e9) if ns > 0 else None
