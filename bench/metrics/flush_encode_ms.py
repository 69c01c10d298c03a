"""Front end, per flush: encoding the batch's requests and padding the
batch, from the program's ``repro.serve.encode`` spans inside each
``repro.serve.flush`` (ms per batch)."""

from bench.metrics._program import mean_per_parent


def read(view):
    v = mean_per_parent(view, "repro.serve.flush", ("repro.serve.encode",))
    return None if v is None else v * 1e3
