"""Operations and bytes of a Ling-3.0-flash-family configuration (Kimi
Delta Attention layers to one latent layer, leading dense MLPs, then
routed experts), from the configuration's keys alone: what the
`*_kda.serve` readers and `kda_scan_roofline.serve` /
`kda_step_roofline.serve` divide by the peaks. `mla_cost.py` multiplies
a latent layer's numbers by the DEPTH, which is wrong where one layer of
six is latent, and `moe_cost.patterns` formats keys this configuration
lacks; both are accepted files and stay as they are. Kept with the
benchmark, apart from the program (`paddle_tpu` computes none of
this)."""
from __future__ import annotations

ITEM = 4     # float32 weights, states and slabs
CHUNK = 64   # tokens the chunked delta rule touches the state once for
SUB = 16     # tokens of a sub-chunk (`paddle_tpu/ops/kda.py`)


def depth(cfg: dict) -> int:
    return int(cfg["num_hidden_layers"])


def kinds(cfg: dict):
    """[("kda" | "latent", "dense" | "sparse")] layer by layer."""
    return [("latent" if (i + 1) % cfg["layer_group_size"] == 0 else "kda",
             "dense" if i < cfg["first_k_dense_replace"] else "sparse")
            for i in range(depth(cfg))]


def n_kda(cfg: dict) -> int:
    return sum(m == "kda" for m, _ in kinds(cfg))


def n_latent(cfg: dict) -> int:
    return depth(cfg) - n_kda(cfg)


def n_sparse(cfg: dict) -> int:
    return sum(f == "sparse" for _, f in kinds(cfg))


def kda_params(cfg: dict) -> int:
    """One KDA mixer: W_q, W_k, W_v, W_o, the full-rank decay projection
    W_f, W_beta and W_g, three convs, A_log, dt_bias, the norm's gain
    (52.7 M at the published widths)."""
    d, h, dk = (cfg["hidden_size"], cfg["num_attention_heads"],
                cfg["head_dim"])
    return (5 * d * h * dk + 2 * d * h
            + 3 * cfg["short_conv_kernel_size"] * h * dk + h + h * dk + dk)


def latent_params(cfg: dict) -> int:
    """The latent mixer: W_q (no bottleneck), W_kva, W_kvb, W_o, W_g and
    the gain on c_kv (32.0 M)."""
    d, h, r = (cfg["hidden_size"], cfg["num_attention_heads"],
               cfg["kv_lora_rank"])
    dn, dr, dv = (cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"],
                  cfg["v_head_dim"])
    return (d * h * (dn + dr) + d * (r + dr) + r * h * (dn + dv)
            + h * dv * d + d * h + r)


def expert_params(cfg: dict) -> int:
    """ONE routed expert: gate, up and down (5.898 M = 23.6 MB)."""
    return 3 * cfg["hidden_size"] * cfg["moe_intermediate_size"]


def sparse_rest_params(cfg: dict) -> int:
    """A sparse layer outside its routed experts: the router with its
    bias and the shared expert(s) (7.21 M)."""
    d = cfg["hidden_size"]
    return (d * cfg["num_experts_scored"] + cfg["num_experts_scored"]
            + cfg["num_shared_experts"] * 3 * d
            * cfg["moe_shared_expert_intermediate_size"])


def row_params(cfg: dict) -> int:
    """Parameters every row passes through, all layers (the routed
    experts and the head apart)."""
    d = cfg["hidden_size"]
    dense = depth(cfg) - n_sparse(cfg)
    return (n_kda(cfg) * kda_params(cfg) + n_latent(cfg) * latent_params(cfg)
            + dense * 3 * d * cfg["intermediate_size"]
            + n_sparse(cfg) * sparse_rest_params(cfg))


def dense_params(cfg: dict) -> int:
    """What a decode step reads whatever it routes: `row_params`, two
    gains a layer, the final gain and the head's own matrix (the table's
    gathered rows are not counted)."""
    d = cfg["hidden_size"]
    return row_params(cfg) + 2 * d * depth(cfg) + d + d * cfg["vocab_size"]


def latent_row_bytes(cfg: dict) -> int:
    """Bytes of one position's latent rows, every latent layer's (2,304
    at a row of 576 floats and one latent layer)."""
    return (n_latent(cfg) * (cfg["kv_lora_rank"] + cfg["qk_rope_head_dim"])
            * ITEM)


def state_bytes_per_slot(cfg: dict) -> int:
    """Bytes of a slot's delta-rule matrix states, every KDA layer's
    (10.49 MB at five layers of 32 x 128 x 128 floats)."""
    return (n_kda(cfg) * cfg["num_attention_heads"] * cfg["head_dim"] ** 2
            * ITEM)


def step_bytes(cfg: dict, experts_active: float, latent_rows: float,
               kda_state_bytes: float) -> float:
    """Bytes one decode step HAS to move: the dense weights, the held
    (layer, expert) that received a pair, once each, the live slots'
    matrix states read and written once, and the live latent rows."""
    return (ITEM * (dense_params(cfg) + experts_active * expert_params(cfg))
            + 2.0 * kda_state_bytes + latent_rows * latent_row_bytes(cfg))


def kda_step_bytes(cfg: dict, kda_state_bytes: float, active: float):
    """Bytes the steps' delta-rule updates HAVE to move, all KDA layers:
    the live states read and written once, and a live slot's q, k, v and
    g rows read and its o row written."""
    row = cfg["num_attention_heads"] * cfg["head_dim"] * ITEM
    return 2.0 * kda_state_bytes + 5.0 * row * n_kda(cfg) * active


def kda_scan_flops_per_token(cfg: dict) -> float:
    """FLOPs the CHUNKED delta rule does a token, a head and a layer at
    chunks of C = 64 (a multiply and an add each): the two decay-weighted
    Gram matrices 4 C dk, the triangular inverse counted C^2, T
    Diag(beta) [V, K~] 4 C dk, the three products that touch the state
    6 dk dv, and A_qk U 2 C dv: 184,320 at dk = dv = 128."""
    dk = cfg["head_dim"]
    return 8.0 * CHUNK * dk + CHUNK * CHUNK + 6.0 * dk * dk \
        + 2.0 * CHUNK * dk


def kda_scan_cost(cfg: dict, tokens: float, prompts: float):
    """(FLOPs, bytes) the chunked scans of `tokens` LIVE tokens in
    `prompts` prompts have to do and move, every KDA layer: per token q,
    k, v and g read and o written; per prompt a state written."""
    h, dk = cfg["num_attention_heads"], cfg["head_dim"]
    flops = n_kda(cfg) * h * kda_scan_flops_per_token(cfg) * tokens
    nbytes = (n_kda(cfg) * 5.0 * h * dk * ITEM * tokens
              + state_bytes_per_slot(cfg) * prompts)
    return flops, nbytes


def prefill_flops(cfg: dict, prompt_rows: float, expert_pairs: float,
                  attn_pairs: float, prompts: float) -> float:
    """Model FLOPs of a prefill's LIVE rows, a multiply and an add each:
    every row through `row_params`; the held (token, expert) pairs the
    program counted; the chunked delta rule of the KDA layers; causal
    attention of the latent layer over the (query, key) pairs of the
    live rows, counted once, score (192) and weighted sum (128), every
    head; the head on one row a prompt. Not the bucket's padding, nor
    the zero channels the flash kernel is handed."""
    d, h = cfg["hidden_size"], cfg["num_attention_heads"]
    return (2.0 * row_params(cfg) * prompt_rows
            + 2.0 * expert_params(cfg) * expert_pairs
            + kda_scan_cost(cfg, prompt_rows, prompts)[0]
            + 2.0 * n_latent(cfg) * h
            * (cfg["qk_head_dim"] + cfg["v_head_dim"]) * attn_pairs
            + 2.0 * d * cfg["vocab_size"] * prompts)


def patterns(cfg: dict) -> dict:
    """Pieces of HLO text by which a device event is told to belong to
    the delta rule (an XLA fusion or loop carries no scope in its name
    on the chip, only its operands' shapes): "state": a matrix state by
    its shape, any batch (`f32[B,32,128,128]`: a step's update, a chunked
    scan's loop carry); "chunks": what only the chunked form builds: a
    chunk's tokens by head (`,64,32,128]`), its Gram matrices and
    inverse (`,32,64,64]`), its state-side operands (`,32,64,128]`) and
    its sub-chunks (`,16,32,128]`)."""
    h, dk = cfg["num_attention_heads"], cfg["head_dim"]
    return {"state": [",%d,%d,%d]" % (h, dk, dk)],
            "chunks": [",%d,%d,%d]" % (CHUNK, h, dk),
                       ",%d,%d,%d]" % (h, CHUNK, CHUNK),
                       ",%d,%d,%d]" % (h, CHUNK, dk),
                       ",%d,%d,%d]" % (SUB, h, dk)]}


def kda_events(cfg: dict, ops, modules, program_spans):
    """[(start, end)] of the device events of the delta rule: inside a
    decode step what touches a matrix state; inside a prefill what
    touches a state or a chunk's tensors (the loops of the chunked scan
    carry the state). -> (step events, scan events)."""
    pats = patterns(cfg)
    decode = program_spans.module_intervals(modules, "ptpu_decode_")
    prefill = program_spans.module_intervals(modules, "ptpu_prefill_")

    def inside(s, spans):
        return any(a <= s < b for a, b in spans)

    step = [(s, s + d) for _, s, d, text in ops
            if inside(s, decode) and any(p in text for p in pats["state"])]
    scan = [(s, s + d) for _, s, d, text in ops
            if inside(s, prefill)
            and any(p in text for p in pats["state"] + pats["chunks"])]
    return step, scan


def _inside(intervals, m0, md):
    """Nanoseconds of the merged `intervals` inside [m0, m0 + md)."""
    from .trace_reduce import subtract, total, union

    return total(intervals) - total(subtract(intervals,
                                              union([(m0, m0 + md)])))


def decode_steps(spans, modules, intervals, program_spans):
    """[(seconds of `intervals` inside the step's program, the counts of
    its `decode.loop.dispatch` phase)] for every traced decode step whose
    phase carries `kda_state_bytes`."""
    out = []
    for name, m0, md in modules:
        if "ptpu_decode_" not in name:
            continue
        counts = program_spans.step_of(spans["host"], m0)
        if counts is not None and "kda_state_bytes" in counts:
            out.append((_inside(intervals, m0, md) * 1e-9, counts))
    return out


def admissions(spans, modules, intervals, program_spans):
    """[(seconds of `intervals` inside the prefill's program, the counts
    of the admission's `decode.loop.scatter` phase, the first that opens
    after the program has started)] for every traced prefill whose phase
    carries `kda_tokens`."""
    scatter = program_spans.LOOP + "scatter"
    scatters = [(s, c) for name, s, _, c, _ in spans["host"]
                if name == scatter and "kda_tokens" in c]
    out = []
    for name, m0, md in sorted(modules, key=lambda m: m[1]):
        if "ptpu_prefill_" not in name:
            continue
        after = [c for s, c in scatters if s >= m0]
        if after:  # else the session ended before its scatter opened
            out.append((_inside(intervals, m0, md) * 1e-9, after[0]))
    return out
