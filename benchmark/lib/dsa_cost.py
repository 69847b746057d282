"""Operations and bytes of a dots3-note-family configuration (latent
attention under a learned indexer in the full layers, latent attention
of another geometry over a window in the sliding ones, a leading dense
MLP, then routed experts), from the configuration's keys alone: what
the `*_dsa.serve` readers, `dsa_decode_roofline.serve`,
`dsa_time_pct.serve` and `latent_ring_time_pct.serve` divide by the
peaks. `mla_cost.py` prices ONE latent geometry, every layer and every
live row, and `moe_cost.patterns` formats keys this configuration spells
otherwise; both are accepted files and stay as they are. Kept with the
benchmark, apart from the program (`paddle_tpu` computes none of
this)."""
from __future__ import annotations

from .ling_cost import _inside

ITEM = 4          # float32 weights, slabs, rings and index keys
INDEX_CHUNK = 16  # index heads whose products a pass holds (`ops/dsa.py`)
BUCKETS = (4096, 8192, 16384)  # a prefill's sequence buckets in the cell


def depth(cfg: dict) -> int:
    return int(cfg["num_hidden_layers"])


def kinds(cfg: dict):
    """[("full" | "sliding", "dense" | "sparse")] layer by layer."""
    return [(cfg["layer_types"][i].split("_")[0],
             "dense" if i < cfg["first_k_dense_replace"] else "sparse")
            for i in range(depth(cfg))]


def n_full(cfg: dict) -> int:
    return sum(m == "full" for m, _ in kinds(cfg))


def n_sliding(cfg: dict) -> int:
    return depth(cfg) - n_full(cfg)


def n_sparse(cfg: dict) -> int:
    return sum(f == "sparse" for _, f in kinds(cfg))


def geometry(cfg: dict, kind: str) -> dict:
    """A layer kind's sizes, from the plain keys or the `swa_` ones."""
    p = "swa_" if kind == "sliding" else ""
    return {"h": cfg[p + "num_attention_heads"], "rq": cfg[p + "q_lora_rank"],
            "r": cfg[p + "kv_lora_rank"], "dn": cfg[p + "qk_nope_head_dim"],
            "dr": cfg[p + "qk_rope_head_dim"], "dv": cfg[p + "v_head_dim"]}


def latent_params(cfg: dict, kind: str) -> int:
    """One latent mixer without an indexer: W_qa, W_qb, W_kva, W_kvb,
    W_o, W_g and the two gains (135.27 M full, 90.83 M sliding)."""
    d, g = cfg["hidden_size"], geometry(cfg, kind)
    return (d * g["rq"] + g["rq"] * g["h"] * (g["dn"] + g["dr"])
            + d * (g["r"] + g["dr"]) + g["r"] * g["h"] * (g["dn"] + g["dv"])
            + g["h"] * g["dv"] * d + d * g["h"] + g["rq"] + g["r"])


def index_params(cfg: dict) -> int:
    """The indexer of a full layer: W_Iq, W_Ik with its LayerNorm, W_Iw
    (9.37 M)."""
    d, j, di = (cfg["hidden_size"], cfg["index_n_heads"],
                cfg["index_head_dim"])
    return cfg["q_lora_rank"] * j * di + d * di + 2 * di + d * j


def mixer_params(cfg: dict, kind: str) -> int:
    """144.05 M a full mixer, 90.83 M a sliding one."""
    return latent_params(cfg, kind) + (index_params(cfg) if kind == "full"
                                       else 0)


def expert_params(cfg: dict) -> int:
    """ONE routed expert: gate, up and down (23.59 M = 94.4 MB)."""
    return 3 * cfg["hidden_size"] * cfg["moe_intermediate_size"]


def sparse_rest_params(cfg: dict) -> int:
    """A sparse layer outside its routed experts: the router with its
    bias and the shared expert(s) (24.90 M)."""
    d = cfg["hidden_size"]
    return (d * cfg["n_routed_experts_scored"]
            + cfg["n_routed_experts_scored"]
            + cfg["n_shared_experts"] * expert_params(cfg))


def row_params(cfg: dict) -> int:
    """Parameters every row passes through, all layers (the routed
    experts and the head apart)."""
    d = cfg["hidden_size"]
    return (n_full(cfg) * mixer_params(cfg, "full")
            + n_sliding(cfg) * mixer_params(cfg, "sliding")
            + (depth(cfg) - n_sparse(cfg)) * 3 * d * cfg["intermediate_size"]
            + n_sparse(cfg) * sparse_rest_params(cfg))


def dense_params(cfg: dict) -> int:
    """What a decode step reads whatever it routes: `row_params`, two
    gains a layer, the final gain and the head's own matrix (the table's
    gathered rows are not counted): 3.88 GB at the cell's sizes."""
    d = cfg["hidden_size"]
    return row_params(cfg) + 2 * d * depth(cfg) + d + d * cfg["vocab_size"]


def weight_params(cfg: dict) -> int:
    """Every parameter the chip holds: `dense_params`, the table, and
    the held experts of every sparse layer (1.822 B = 7.29 GB)."""
    held = cfg["experts_held"][1] - cfg["experts_held"][0]
    return (dense_params(cfg) + cfg["hidden_size"] * cfg["vocab_size"]
            + n_sparse(cfg) * held * expert_params(cfg))


def latent_row_bytes(cfg: dict) -> int:
    """One position's latent row of ONE full layer (2,304 B)."""
    return (cfg["kv_lora_rank"] + cfg["qk_rope_head_dim"]) * ITEM


def index_key_bytes(cfg: dict) -> int:
    """One position's index key of ONE full layer (512 B)."""
    return cfg["index_head_dim"] * ITEM


def ring_row_bytes(cfg: dict) -> int:
    """One row of ONE sliding layer's ring (4,352 B)."""
    return (cfg["swa_kv_lora_rank"] + cfg["swa_qk_rope_head_dim"]) * ITEM


def slot_bytes(cfg: dict) -> int:
    """What a slot keeps: every full layer's latent slab and index keys
    at `serve.max_seq` positions, every sliding layer's ring (99.0
    MB)."""
    return (n_full(cfg) * cfg["serve"]["max_seq"]
            * (latent_row_bytes(cfg) + index_key_bytes(cfg))
            + n_sliding(cfg) * cfg["sliding_window_size"]
            * ring_row_bytes(cfg))


def dsa_step_bytes(cfg: dict, rows_live: float, rows_chosen: float):
    """Bytes a step's full layers MUST read of their caches, all such
    layers: every live row's index key (the indexer scores them all)
    and the chosen rows' latent rows. An implementation that streams
    every live latent row, or every row of every slot, reads more."""
    return n_full(cfg) * (rows_live * index_key_bytes(cfg)
                          + rows_chosen * latent_row_bytes(cfg))


def ring_step_bytes(cfg: dict, ring_rows: float):
    """Bytes a step's sliding layers read of their rings, all such
    layers: each live slot's `min(length + 1, window)` rows."""
    return n_sliding(cfg) * ring_rows * ring_row_bytes(cfg)


def step_bytes(cfg: dict, experts_active: float, rows_live: float,
               rows_chosen: float, ring_rows: float) -> float:
    """Bytes one decode step HAS to read: the dense weights, the held
    (layer, expert) that received a pair, once each, and of the caches
    `dsa_step_bytes` and `ring_step_bytes`."""
    return (ITEM * (dense_params(cfg) + experts_active * expert_params(cfg))
            + dsa_step_bytes(cfg, rows_live, rows_chosen)
            + ring_step_bytes(cfg, ring_rows))


def prefill_flops(cfg: dict, prompt_rows: float, expert_pairs: float,
                  index_pairs: float, chosen_pairs: float,
                  window_pairs: float, prompts: float) -> float:
    """Model FLOPs of a prefill's LIVE rows, a multiply and an add each:
    every row through `row_params`; the held (token, expert) pairs the
    program counted; the indexer's 64 heads of 128 over every (query,
    key) pair under the causal mask (`index_pairs`, one full layer's);
    attention over the pairs a full layer KEEPS (`chosen_pairs`: a query
    at t keeps min(t + 1, index_topk)) and over a sliding layer's window
    (`window_pairs`: min(t + 1, sliding_window_size)), score and
    weighted sum at each kind's own heads and widths; the head on one
    row a prompt. Not the bucket's padding, nor the pairs the flash
    kernel computes and masks, nor the zero channels it is handed."""
    f, s = geometry(cfg, "full"), geometry(cfg, "sliding")
    return (2.0 * row_params(cfg) * prompt_rows
            + 2.0 * expert_params(cfg) * expert_pairs
            + 2.0 * n_full(cfg) * cfg["index_n_heads"]
            * cfg["index_head_dim"] * index_pairs
            + 2.0 * n_full(cfg) * f["h"] * (f["dn"] + f["dr"] + f["dv"])
            * chosen_pairs
            + 2.0 * n_sliding(cfg) * s["h"] * (s["dn"] + s["dr"] + s["dv"])
            * window_pairs
            + 2.0 * cfg["hidden_size"] * cfg["vocab_size"] * prompts)


# pieces of a Mosaic call's name: a prefill's flash calls under the mask
# and a step's attention over the chosen rows (`ptpu.dsa_attend`,
# `ptpu.dsa_attend_step`), a step's index scores (`ptpu.dsa_index_step`)
DSA_KERNELS = ("ptpu.dsa_attend", "ptpu.dsa_index_step")
RING_KERNELS = ("ptpu.latent_ring_attend",)


def patterns(cfg: dict) -> dict:
    """Pieces of HLO text by which a device event is told (an XLA fusion
    or loop carries no scope in its name on the chip, only its operands'
    shapes; a Pallas kernel carries its name). "dsa": what only the
    indexer, the choice and the attention under it build or read: the
    choice's ordered bits (`u32[` of a shape: nothing else in the
    programs is unsigned), a prefill's mask (`s8[`), the index queries
    by head (`,64,128]`), a chunk of index heads' products on a slab or
    a bucket (`,16,16384]`), the slab of index keys and the latent slab
    at the cell's (slots, seq), and a step's scores of every head on
    every row (`f32[32,128,16384]`). "ring": a sliding layer's ring by
    its shape, any batch (`,513,1088]`)."""
    slots, seq = cfg["serve"]["slots"], cfg["serve"]["max_seq"]
    j, di = cfg["index_n_heads"], cfg["index_head_dim"]
    row = cfg["kv_lora_rank"] + cfg["qk_rope_head_dim"]
    ring = cfg["swa_kv_lora_rank"] + cfg["swa_qk_rope_head_dim"]
    chunk = INDEX_CHUNK if j % INDEX_CHUNK == 0 else j
    dsa = (["u32[%d" % n for n in range(1, 10)] + ["s8["]
           + [",%d,%d]" % (j, di)]
           + [",%d,%d]" % (chunk, t) for t in BUCKETS]
           + ["f32[%d,%d,%d]" % (slots, seq, w) for w in (di, row)]
           + ["f32[%d,%d,%d]" % (slots, cfg["num_attention_heads"], seq),
              "pred[%d,%d]" % (slots, seq)])
    return {"dsa": dsa, "ring": [",%d,%d]" % (cfg["sliding_window_size"],
                                              ring)]}


def _told(ops, pats, kernels):
    """A loop's own event is left out: its body's events are told one by
    one, and the loop over a prefill's groups of heads carries the mask
    beside the projections, which are any attention's."""
    return [(s, s + d) for n, s, d, text in ops
            if not n.startswith("while")
            and (any(k in n for k in kernels)
                 or any(p in text for p in pats))]


def dsa_events(cfg: dict, ops):
    """[(start, end)] of the device events of the indexer, the choice
    and the attention under it, prefills and steps alike."""
    return _told(ops, patterns(cfg)["dsa"], DSA_KERNELS)


def ring_events(cfg: dict, ops):
    """[(start, end)] of the device events of the sliding layers'
    attention: the window's flash calls and what touches a ring."""
    return _told(ops, patterns(cfg)["ring"], RING_KERNELS)


def decode_steps(spans, modules, intervals, program_spans):
    """[(seconds of `intervals` inside the step's program, the counts of
    its `decode.loop.dispatch` phase)] for every traced decode step whose
    phase carries `rows_chosen`."""
    out = []
    for name, m0, md in modules:
        if "ptpu_decode_" not in name:
            continue
        counts = program_spans.step_of(spans["host"], m0)
        if counts is not None and "rows_chosen" in counts:
            out.append((_inside(intervals, m0, md) * 1e-9, counts))
    return out


def admissions(spans, modules, intervals, program_spans):
    """[(seconds of `intervals` inside the prefill's program, the counts
    of the admission's `decode.loop.scatter` phase, the first that opens
    after the program has started)] for every traced prefill whose phase
    carries `chosen_pairs`."""
    scatter = program_spans.LOOP + "scatter"
    scatters = [(s, c) for name, s, _, c, _ in spans["host"]
                if name == scatter and "chosen_pairs" in c]
    out = []
    for name, m0, md in sorted(modules, key=lambda m: m[1]):
        if "ptpu_prefill_" not in name:
            continue
        after = [c for s, c in scatters if s >= m0]
        if after:  # else the session ended before its scatter opened
            out.append((_inside(intervals, m0, md) * 1e-9, after[0]))
    return out
