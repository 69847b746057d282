"""Operations and bytes of a Solar-Open2-family configuration (delta-rule
KDA layers under an unbounded decay gate to one gated softmax layer of
fewer key/value heads than query heads, routed experts with a shared
one in every layer), from the configuration's keys alone: what the
`*_kdagqa.serve` readers, `kda_guarded_scan_roofline.serve`,
`kda_wide_step_roofline.serve` and `decode_attn_nope_roofline.serve`
divide by the peaks, the same whatever implements a kernel.
`lib/ling_cost.py` counts from Ling's keys (`layer_group_size`,
`kv_lora_rank`, `num_attention_heads` as the KDA heads) and is an
accepted file; the chunked scan's operations are counted as it counts
them, so the two cells' scans stand against one yardstick. Kept with
the benchmark, apart from the program (`paddle_tpu` computes none of
this)."""
from __future__ import annotations

# the join of a trace's programs with the server's counts is Ling's: a
# decode step by `kda_state_bytes` on its `dispatch` phase
from .ling_cost import _inside, decode_steps  # noqa: F401

ITEM = 4     # float32 weights, states and slabs
CHUNK = 64   # tokens the chunked delta rule touches the state once for

# the kernels' own names: a Mosaic call's event is named after its scope
KDA_SCAN = "ptpu.kda_scan"
KDA_STEP = "ptpu.kda_step"
DECODE_ATTN = "ptpu.decode_attn_grouped"


def is_family(cfg: dict) -> bool:
    return "gqa_layers" in cfg and "linear_attn_config" in cfg


def depth(cfg: dict) -> int:
    return int(cfg["num_hidden_layers"])


def kinds(cfg: dict):
    """"gqa" | "kda" layer by layer."""
    return ["gqa" if i in cfg["gqa_layers"] else "kda"
            for i in range(depth(cfg))]


def n_kda(cfg: dict) -> int:
    return kinds(cfg).count("kda")


def n_gqa(cfg: dict) -> int:
    return kinds(cfg).count("gqa")


def kda_width(cfg: dict) -> int:
    """H * dk of a KDA layer's q, k, v and g rows (8,192)."""
    lin = cfg["linear_attn_config"]
    return lin["num_heads"] * lin["head_dim"]


def kda_params(cfg: dict) -> int:
    """One KDA mixer: W_q, W_k, W_v, W_o; the decay's and the output
    gate's bottlenecks; W_beta; three convs; A_log, dt_bias, the norm's
    gain (137.73 M at the published widths)."""
    d, lin = cfg["hidden_size"], cfg["linear_attn_config"]
    h, dk, w = lin["num_heads"], lin["head_dim"], kda_width(cfg)
    r = int(cfg["assumed"]["kda_rank"])
    return (4 * d * w + 2 * (d * r + r * w) + d * h
            + 3 * lin["short_conv_kernel_size"] * w + h + w + dk)


def gqa_params(cfg: dict) -> int:
    """The softmax mixer: W_q, W_o and the elementwise gate W_g at the
    query heads' width, W_k and W_v at the key/value heads' (109.05 M)."""
    d, dh = cfg["hidden_size"], cfg["head_dim"]
    return (3 * d * cfg["num_attention_heads"] * dh
            + 2 * d * cfg["num_key_value_heads"] * dh)


def expert_params(cfg: dict) -> int:
    """ONE routed expert: gate, up and down (15.73 M = 62.9 MB)."""
    return 3 * cfg["hidden_size"] * cfg["moe_intermediate_size"]


def layer_rest_params(cfg: dict) -> int:
    """An expert layer outside its routed experts: the router and the
    shared expert (17.04 M)."""
    d = cfg["hidden_size"]
    return (d * cfg["n_routed_experts_scored"]
            + cfg["n_shared_experts"] * 3 * d * cfg["moe_intermediate_size"])


def row_params(cfg: dict) -> int:
    """Parameters every row passes through, all layers (the routed
    experts and the head apart)."""
    return (n_kda(cfg) * kda_params(cfg) + n_gqa(cfg) * gqa_params(cfg)
            + depth(cfg) * layer_rest_params(cfg))


def dense_params(cfg: dict) -> int:
    """What a decode step reads whatever it routes: `row_params`, two
    gains a layer, the final gain and the head's own matrix (the table's
    gathered rows are not counted)."""
    d = cfg["hidden_size"]
    return row_params(cfg) + 2 * d * depth(cfg) + d + d * cfg["vocab_size"]


def kv_row_bytes(cfg: dict) -> int:
    """Bytes of K and V one attended position costs a step, every
    softmax layer's (8,192 at 8 heads of 128 and one such layer)."""
    return (n_gqa(cfg) * 2 * cfg["num_key_value_heads"] * cfg["head_dim"]
            * ITEM)


def state_bytes_per_slot(cfg: dict) -> int:
    """Bytes of a slot's delta-rule matrix states, every KDA layer's
    (12.58 MB at three layers of 64 x 128 x 128 floats)."""
    lin = cfg["linear_attn_config"]
    return n_kda(cfg) * lin["num_heads"] * lin["head_dim"] ** 2 * ITEM


def step_bytes(cfg: dict, experts_active: float, kv_rows: float,
               kda_state_bytes: float) -> float:
    """Bytes one decode step HAS to move: the dense weights, the held
    (layer, expert) that received a pair, once each, the live slots'
    matrix states read and written once, and the live K/V rows."""
    return (ITEM * (dense_params(cfg) + experts_active * expert_params(cfg))
            + 2.0 * kda_state_bytes + kv_rows * kv_row_bytes(cfg))


def kda_step_bytes(cfg: dict, kda_state_bytes: float, active: float):
    """Bytes the steps' delta-rule updates HAVE to move, all KDA layers:
    the live states read and written once, and a live slot's q, k, v and
    g rows read and its o row written."""
    return (2.0 * kda_state_bytes
            + 5.0 * kda_width(cfg) * ITEM * n_kda(cfg) * active)


def kda_scan_flops_per_token(cfg: dict) -> float:
    """FLOPs the CHUNKED delta rule does a token, a head and a layer at
    chunks of C = 64 (a multiply and an add each), as `lib/ling_cost.py`
    counts them for the factored form (the guarded form does the same
    algebra; a sub-chunk's own block by multiplies and adds is no more
    operations, only another unit): the two decay-weighted Gram matrices
    4 C dk, the triangular inverse counted C^2, T Diag(beta) [V, K~]
    4 C dk, the three products that touch the state 6 dk dv, and A_qk U
    2 C dv: 184,320 at dk = dv = 128. The exponentials are not
    counted."""
    dk = cfg["linear_attn_config"]["head_dim"]
    return 8.0 * CHUNK * dk + CHUNK * CHUNK + 6.0 * dk * dk \
        + 2.0 * CHUNK * dk


def kda_scan_cost(cfg: dict, tokens: float, prompts: float):
    """(FLOPs, bytes) the chunked scans of `tokens` LIVE tokens in
    `prompts` prompts have to do and move, every KDA layer: per token q,
    k, v and g read and o written; per prompt a state written."""
    h = cfg["linear_attn_config"]["num_heads"]
    flops = n_kda(cfg) * h * kda_scan_flops_per_token(cfg) * tokens
    nbytes = (n_kda(cfg) * 5.0 * kda_width(cfg) * ITEM * tokens
              + state_bytes_per_slot(cfg) * prompts)
    return flops, nbytes


def prefill_flops(cfg: dict, prompt_rows: float, expert_pairs: float,
                  attn_pairs: float, prompts: float) -> float:
    """Model FLOPs of a prefill's LIVE rows, a multiply and an add each:
    every row through `row_params`; the held (token, expert) pairs the
    program counted; the chunked delta rule of the KDA layers; causal
    attention of the softmax layer over the (query, key) pairs of the
    live rows, counted once, score and weighted sum, every query head;
    the head on one row a prompt. Not the bucket's padding."""
    d, dh = cfg["hidden_size"], cfg["head_dim"]
    return (2.0 * row_params(cfg) * prompt_rows
            + 2.0 * expert_params(cfg) * expert_pairs
            + kda_scan_cost(cfg, prompt_rows, prompts)[0]
            + 2.0 * n_gqa(cfg) * cfg["num_attention_heads"] * 2 * dh
            * attn_pairs
            + 2.0 * d * cfg["vocab_size"] * prompts)


def kernel_events(ops, kernel: str):
    """[(start, end)] of the Mosaic calls named `kernel`: by the call's
    own name (a consumer's text names it as an operand)."""
    return [(s, s + d) for n, s, d, _ in ops if kernel in n]


def admissions(spans, modules, intervals, program_spans):
    """[(seconds of `intervals` inside the prefill's program, the counts
    of the admission's `decode.loop.scatter` phase, the first that opens
    after the program has started)] for every traced prefill whose phase
    carries `kda_tokens` and `attn_pairs`."""
    scatter = program_spans.LOOP + "scatter"
    scatters = [(s, c) for name, s, _, c, _ in spans["host"]
                if name == scatter and "kda_tokens" in c
                and "attn_pairs" in c]
    out = []
    for name, m0, md in sorted(modules, key=lambda m: m[1]):
        if "ptpu_prefill_" not in name:
            continue
        after = [c for s, c in scatters if s >= m0]
        if after:  # else the session ended before its scatter opened
            out.append((_inside(intervals, m0, md) * 1e-9, after[0]))
    return out


# what an event's scopes, its fusion's members, the scopes its result
# goes to and the weights it reads are searched for, mixer by mixer
_MARKS = {
    "kda": (".kda.", "ptpu.kda_", "fl.kda_"),
    "gqa": (".attention.", "ptpu.flash_fwd", "ptpu.decode_attn",
            "ptpu.prefill_attn", "fl.prefill_attention",
            "fl.decode_attention", "fl.cache_append:"),
    "experts": (".moe.", "ptpu.moe_", "fl.moe_"),
}


def of_mixer(which: str):
    """`scope_time.select_s`'s predicate: the events of the KDA layers'
    mixers ("kda"), the softmax layer's ("gqa") or the expert layers'
    ("experts"), told by a scope or a weight of theirs (`_MARKS`). An
    elementwise event between two mixers, anchored at a temporary's
    name, belongs to none: the three shares are lower bounds."""
    marks = _MARKS[which]

    def want(entry, m):
        if entry is None:
            return False
        names = (list(entry["scope"]) + list(entry["members"])
                 + list(entry.get("users", ())) + list(entry["reads"]))
        return any(mark in n for n in names for mark in marks)

    return want
