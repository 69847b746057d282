"""The decode step's share of its memory roofline in the traced steps:
the least time the chip could take for them, over the time it was busy
inside their programs. A step has to read every weight of the decode
graph once (`lib/serve_bytes.decode_weight_params` x 4 bytes), read and
write every slot's fixed-size state (`state_bytes`, the count the
step's `decode.loop.dispatch` phase carries) and read the K and V rows
it attends (`attended`, the same phase's count, x
`lib/serve_bytes.kv_row_bytes`); all of it over the HBM peak is the
least time. The time spent is the union of the operation events inside
the `jit_ptpu_decode_*` module events of the same steps (first chip).
A step of 64 slots is memory-bound: 64 rows against every weight.
Nothing where the program's phases carry no `state_bytes` (a program
older than the count) or the configuration is not of this family."""
from benchmark.lib import program_spans, serve_bytes
from benchmark.lib.trace_reduce import subtract, total, union

LAYER = "model step"
UNIT = "%"
MOVES = "serve_tokens_per_s"
SOURCE = "device_trace"


def read(run):
    spans = program_spans.of_run(run)
    cfg = run["cfg"]
    if not spans or "mamba_d_state" not in cfg:
        return None
    ops = program_spans.first_device(spans["ops"])
    modules = program_spans.first_device(spans["modules"])
    layers = cfg["num_hidden_layers"]
    n_layer = int(layers["serve"] if isinstance(layers, dict) else layers)
    weights = 4 * serve_bytes.decode_weight_params(cfg, n_layer)
    row = serve_bytes.kv_row_bytes(cfg, n_layer)
    busy = union((s, s + d) for _, s, d, _ in ops)
    least = spent = 0.0
    n = 0
    for name, m0, md in modules:
        if "ptpu_decode_" not in name:
            continue
        step = program_spans.step_of(spans["host"], m0)
        if step is None or "state_bytes" not in step:
            continue
        inside = union([(m0, m0 + md)])
        spent += (total(busy) - total(subtract(busy, inside))) * 1e-9
        least += (weights + float(step["state_bytes"])
                  + float(step["attended"]) * row) / run["peaks"][
                      "hbm_bytes_per_s"]
        n += 1
    if not n or spent <= 0:
        return None
    print("decode_step_roofline: %d steps, %.6f s busy in the trace, %.6f s "
          "at the HBM peak (%.3f GB of weights a step)"
          % (n, spent, least, weights / 1e9), flush=True)
    return 100.0 * least / spent
