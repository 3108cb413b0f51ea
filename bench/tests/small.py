"""The cell cut to a size a CPU test run holds: the same code paths, fewer
layers, narrower widths, fewer clients and shorter calls.  The LM is run in
float32 here so that a sound run reads near zero and every planted fault
stands out against the cell's limits."""
from bench import harness

SMALL = {
    "mamba2_130m.paper_fed": (
        dict(n_layer=2, d_model=64, d_state=16, headdim=16, vocab_size=512,
             chunk_size=16, dtype="float32"),
        dict(federation=dict(n_clients=8, concurrency=4),
             data=dict(batch=2, seq_len=64, shard_rows=8, eval_rows=2),
             engine=dict(updates_per_call=6, check_calls=2, trace_calls=1)),
    ),
}


def small_spec(cell: str) -> harness.Spec:
    spec = harness.load_spec(cell)
    sizes, traffic = SMALL[cell]
    spec.sizes.update(sizes)
    for k, v in traffic.items():
        spec.traffic[k].update(v)
    return spec
