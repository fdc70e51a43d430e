"""Train loop: mean host time of the production of one batch inside the
loader (the program's ``ddp:loader.batch`` span: host gather plus device
placement), over the batches made in the traced window."""

from benchmarks import scope_reduce


def read(ctx):
    reduced = scope_reduce.for_ctx(ctx)
    spans = reduced and reduced["spans"].get("loader.batch")
    if not spans:
        return None
    return 1e3 * sum(spans) / len(spans)
