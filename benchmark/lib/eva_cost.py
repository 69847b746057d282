"""Operations and bytes of an EvaByte-family configuration (every layer
EVA attention: a window's exact rows and one pooled row a chunk of every
earlier window, in ONE array for K and one for V a slot and layer; a
gated-SiLU MLP; an untied head), from the configuration's keys alone:
what `eva_time_pct.serve`, `eva_decode_roofline.serve`,
`eva_summary_rows_pct.serve`, `decode_step_roofline_eva.serve` and
`prefill_mfu_pct_eva.serve` divide by the peaks. Kept with the
benchmark, apart from the program (`paddle_tpu` computes none of
this)."""
from __future__ import annotations

from .ling_cost import _inside

ITEM = 4  # float32 weights and cached rows


def depth(cfg: dict) -> int:
    return int(cfg["num_hidden_layers"])


def head_dim(cfg: dict) -> int:
    return cfg["hidden_size"] // cfg["num_attention_heads"]


def matmul_params(cfg: dict) -> int:
    """The matrices every row of ONE layer passes through: W_q, W_k,
    W_v, W_o and the MLP's three (202.38 M)."""
    d = cfg["hidden_size"]
    return 4 * d * d + 3 * d * cfg["intermediate_size"]


def layer_params(cfg: dict) -> int:
    """One layer: its matrices, two gains and a head's phi and mu
    (202.39 M = 809.6 MB)."""
    return (matmul_params(cfg) + 2 * cfg["hidden_size"]
            + 2 * cfg["num_attention_heads"] * head_dim(cfg))


def dense_params(cfg: dict) -> int:
    """What a decode step reads of the weights: every layer, the final
    gain and the head's own matrix (the table's gathered rows are not
    counted): 810.9 M = 3.24 GB at the cell's depth."""
    d = cfg["hidden_size"]
    return depth(cfg) * layer_params(cfg) + d + d * cfg["vocab_size"]


def weight_params(cfg: dict) -> int:
    """Every parameter the chip holds: `dense_params` and the table."""
    return dense_params(cfg) + cfg["hidden_size"] * cfg["vocab_size"]


def row_bytes(cfg: dict) -> int:
    """One row of ONE entry (K's or V's): every head's channels
    (16,384 B)."""
    return cfg["num_attention_heads"] * head_dim(cfg) * ITEM


def entry_rows(cfg: dict) -> int:
    """Rows of an entry: a summary a chunk of `serve.max_seq` positions,
    then the window's block (3,072)."""
    return (cfg["serve"]["max_seq"] // cfg["chunk_size"]
            + cfg["window_size"])


def slot_bytes(cfg: dict) -> int:
    """What a slot keeps: K's and V's entry of every layer (402.7 MB)."""
    return depth(cfg) * 2 * entry_rows(cfg) * row_bytes(cfg)


def eva_step_bytes(cfg: dict, window_rows: float, summary_rows: float):
    """Bytes a step's attention MUST read of the caches, all layers: K
    and V of every live window row and visible summary row (the counts
    are one layer's; every layer reads as many). An implementation that
    streams whole blocks of 128 rows, or every row of every entry, reads
    more."""
    return depth(cfg) * 2 * row_bytes(cfg) * (window_rows + summary_rows)


def step_bytes(cfg: dict, window_rows: float, summary_rows: float) -> float:
    """Bytes one decode step HAS to read: the weights once and
    `eva_step_bytes`."""
    return (ITEM * dense_params(cfg)
            + eva_step_bytes(cfg, window_rows, summary_rows))


def prefill_flops(cfg: dict, prompt_rows: float, attn_pairs: float,
                  prompts: float) -> float:
    """Model FLOPs of a prefill's LIVE rows, a multiply and an add each:
    every row through every layer's matrices; attention's score and
    weighted sum over the (query, key or summary) pairs one layer
    attends (`attn_pairs`: a query at t sees `t mod W + 1` keys and 128
    summaries a closed window), every head, every layer; the head on one
    row a prompt. Not the bucket's padding, nor the pairs the flash
    kernel computes and masks, nor the pooling (multiplies and adds on
    the vector units, 0.1% of a row's matmuls)."""
    h, dh = cfg["num_attention_heads"], head_dim(cfg)
    return (2.0 * depth(cfg) * matmul_params(cfg) * prompt_rows
            + 2.0 * depth(cfg) * h * 2 * dh * attn_pairs
            + 2.0 * cfg["hidden_size"] * cfg["vocab_size"] * prompts)


# pieces of a Mosaic call's name: a step's attention over the live range
# and a prefill's flash calls (own window; summaries)
ATTN_KERNEL = "ptpu.eva_attn"
KERNELS = (ATTN_KERNEL, "ptpu.eva_prefill")


def patterns(cfg: dict) -> list:
    """Pieces of HLO text by which a device event of EVA's lax parts is
    told (an XLA fusion carries no scope in its name on the chip, only
    its operands' shapes; a Pallas kernel carries its name): an entry by
    its rows, heads and width at any batch (`,3072,32,128]`: a step's
    appends, a prefill's packing) and a chunk's rows (`,16,32,128]`: the
    pooling, a prefill's of every chunk and a step's of the one its
    position may close)."""
    h, dh = cfg["num_attention_heads"], head_dim(cfg)
    return [",%d,%d,%d]" % (entry_rows(cfg), h, dh),
            ",%d,%d,%d]" % (cfg["chunk_size"], h, dh)]


def eva_events(cfg: dict, ops, kernels=KERNELS, pats=None):
    """[(start, end)] of the device events of EVA's four scopes
    (`ptpu.eva_summaries`, `ptpu.eva_prefill`, `ptpu.eva_append`,
    `ptpu.eva_attn`), prefills and steps alike: the kernels by name, the
    lax parts by `patterns`. A loop's own event is left out (its body's
    events are told one by one)."""
    pats = patterns(cfg) if pats is None else pats
    return [(s, s + d) for n, s, d, text in ops
            if not n.startswith("while")
            and (any(k in n for k in kernels)
                 or any(p in text for p in pats))]


def attn_kernel_events(ops):
    """[(start, end)] of the step's attention kernel alone."""
    return eva_events({}, ops, kernels=(ATTN_KERNEL,), pats=[])


def decode_steps(spans, modules, intervals, program_spans):
    """[(seconds of `intervals` inside the step's program, the counts of
    its `decode.loop.dispatch` phase)] for every traced decode step whose
    phase carries `eva_window_rows`."""
    out = []
    for name, m0, md in modules:
        if "ptpu_decode_" not in name:
            continue
        counts = program_spans.step_of(spans["host"], m0)
        if counts is not None and "eva_window_rows" in counts:
            out.append((_inside(intervals, m0, md) * 1e-9, counts))
    return out


def admissions(spans, modules, intervals, program_spans):
    """[(seconds of `intervals` inside the prefill's program, the counts
    of the admission's `decode.loop.scatter` phase, the first that opens
    after the program has started)] for every traced prefill whose phase
    carries `eva_summary_rows`."""
    scatter = program_spans.LOOP + "scatter"
    scatters = [(s, c) for name, s, _, c, _ in spans["host"]
                if name == scatter and "eva_summary_rows" in c]
    out = []
    for name, m0, md in sorted(modules, key=lambda m: m[1]):
        if "ptpu_prefill_" not in name:
            continue
        after = [c for s, c in scatters if s >= m0]
        if after:  # else the session ended before its scatter opened
            out.append((_inside(intervals, m0, md) * 1e-9, after[0]))
    return out
