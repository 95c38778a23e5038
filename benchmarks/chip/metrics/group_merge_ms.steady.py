"""The host's merge of the per-chip launches (the program's group_merge span
inside its query span) per batch in the window, ms (cells on several
chips)."""
from chipbench.program_spans import of


def read(ctx):
    ps = of(ctx)
    return None if ps is None else ps.per_batch_ms("query", ["group_merge"])
