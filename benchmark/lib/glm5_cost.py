"""Operations and bytes of a GLM-5-family configuration (latent
attention under a learned indexer in EVERY layer, leading dense MLPs and
then routed experts with a shared one, ONE multi-token-prediction layer
of the same kind behind `eh_proj`, matrices held in
`precision.matrices`), from the configuration's keys alone: what
`decode_step_roofline_mtp.serve`, `dsa_window_roofline.serve` and
`prefill_mfu_pct_mtp.serve` divide by the peaks, and how the traced
ROUNDS of a server that drafts with the prediction layer are found
(`jit_ptpu_round_*` programs; `dsa_cost.decode_steps`, an accepted file,
looks for `ptpu_decode_` and prices dots3's two layer kinds). Kept with
the benchmark, apart from the program (`paddle_tpu` computes none of
this)."""
from __future__ import annotations

from .ling_cost import _inside

SLAB_ITEM = 4  # float32 latent rows and index keys
ROUND = "ptpu_round_"
# pieces of a Mosaic call's name: a round's attention over each
# position's chosen rows and its index scores (`ops/mla.py`,
# `ops/dsa.py`: the step's kernels on a window of two query rows)
WINDOW_KERNELS = ("ptpu.dsa_attend_step", "ptpu.dsa_index_step")


def matrix_item(cfg: dict) -> int:
    """Bytes of one element of a matrix as it is held."""
    return {"float32": 4, "bfloat16": 2}[cfg["precision"]["matrices"]]


def depth(cfg: dict) -> int:
    return int(cfg["num_hidden_layers"])


def n_dense(cfg: dict) -> int:
    return int(cfg.get("dense_layers_built", cfg["first_k_dense_replace"]))


def n_sparse(cfg: dict) -> int:
    """The model's own sparse layers (the prediction layer apart)."""
    return depth(cfg) - n_dense(cfg)


def n_predict(cfg: dict) -> int:
    return int(cfg["num_nextn_predict_layers"])


def n_mixers(cfg: dict) -> int:
    """Layers that keep a latent slab and a slab of index keys: the
    model's and the prediction layer's."""
    return depth(cfg) + n_predict(cfg)


def mixer_matrices(cfg: dict) -> int:
    """One mixer's matrices: W_qa, W_qb, W_kva, W_kvb, W_o and the
    indexer's W_Iq, W_Ik, W_Iw (174.39 M at the published widths)."""
    d, h = cfg["hidden_size"], cfg["num_attention_heads"]
    rq, r = cfg["q_lora_rank"], cfg["kv_lora_rank"]
    dn, dr, dv = (cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"],
                  cfg["v_head_dim"])
    j, di = cfg["index_n_heads"], cfg["index_head_dim"]
    return (d * rq + rq * h * (dn + dr) + d * (r + dr) + r * h * (dn + dv)
            + h * dv * d + rq * j * di + d * di + d * j)


def mixer_gains(cfg: dict) -> int:
    """One layer's float32 vectors: two norms of the layer, the two
    latents' gains, the index key's gain and bias."""
    return (2 * cfg["hidden_size"] + cfg["q_lora_rank"]
            + cfg["kv_lora_rank"] + 2 * cfg["index_head_dim"])


def mlp_matrices(cfg: dict) -> int:
    """The dense MLP: gate, up, down (226.49 M)."""
    return 3 * cfg["hidden_size"] * cfg["intermediate_size"]


def expert_matrices(cfg: dict) -> int:
    """ONE expert, routed or shared: gate, up, down (37.75 M)."""
    return 3 * cfg["hidden_size"] * cfg["moe_intermediate_size"]


def router_floats(cfg: dict) -> int:
    """A sparse layer's float32 router and its selection bias."""
    e = cfg["n_routed_experts_scored"]
    return cfg["hidden_size"] * e + e


def held(cfg: dict) -> int:
    return cfg["experts_held"][1] - cfg["experts_held"][0]


def row_matrices(cfg: dict) -> int:
    """Matrix elements EVERY row of a program passes through, the
    routed experts and the head apart: the mixers (the prediction
    layer's too), the dense MLPs, the shared experts, `eh_proj`."""
    d = cfg["hidden_size"]
    sparse = n_sparse(cfg) + n_predict(cfg)
    return (n_mixers(cfg) * mixer_matrices(cfg)
            + n_dense(cfg) * mlp_matrices(cfg)
            + sparse * cfg["n_shared_experts"] * expert_matrices(cfg)
            + n_predict(cfg) * 2 * d * d)


def row_floats(cfg: dict) -> int:
    """float32 elements every row passes through: routers, gains, the
    final norm and the prediction layer's three."""
    sparse = n_sparse(cfg) + n_predict(cfg)
    return (n_mixers(cfg) * mixer_gains(cfg) + sparse * router_floats(cfg)
            + cfg["hidden_size"] * (1 + 3 * n_predict(cfg)))


def weight_bytes(cfg: dict) -> int:
    """Everything the chip holds of the model: `row_matrices`, the held
    experts, the table and the head in the matrices' type; the float32
    rest (6.59 GB at the cell's sizes in bfloat16)."""
    sparse = n_sparse(cfg) + n_predict(cfg)
    matrices = (row_matrices(cfg)
                + sparse * held(cfg) * expert_matrices(cfg)
                + 2 * cfg["hidden_size"] * cfg["vocab_size"])
    return matrix_item(cfg) * matrices + 4 * row_floats(cfg)


def latent_row_bytes(cfg: dict) -> int:
    """One position's latent row of ONE layer (2,304 B)."""
    return (cfg["kv_lora_rank"] + cfg["qk_rope_head_dim"]) * SLAB_ITEM


def index_key_bytes(cfg: dict) -> int:
    """One position's index key of ONE layer (512 B)."""
    return cfg["index_head_dim"] * SLAB_ITEM


def slot_bytes(cfg: dict) -> int:
    """What a slot keeps: every mixer's latent slab and index keys at
    `serve.max_seq` positions (276.8 MB at six layers of 16,384)."""
    return (n_mixers(cfg) * cfg["serve"]["max_seq"]
            * (latent_row_bytes(cfg) + index_key_bytes(cfg)))


def window_bytes(cfg: dict, rows_live: float, rows_chosen: float) -> float:
    """Bytes a round's mixers MUST read of their slabs, all layers: every
    live row's index key once (both positions score them from one
    fetch) and the latent rows the FIRST position keeps (`min(live,
    index_topk)` a slot; the second position's choice is its own and may
    add rows: not counted, so no reading can pass 100%). An
    implementation that streams every live latent row reads more."""
    return n_mixers(cfg) * (rows_live * index_key_bytes(cfg)
                            + rows_chosen * latent_row_bytes(cfg))


def round_bytes(cfg: dict, experts_active: float, rows_live: float,
                rows_chosen: float) -> float:
    """Bytes one ROUND has to read: every matrix outside the routed
    experts once, whatever its two positions (the head once for both,
    and once more for the prediction layer's logits: the same matrix,
    counted ONCE), the held (layer, expert) that received a pair once
    each, the float32 routers and gains, and `window_bytes`."""
    head = cfg["hidden_size"] * cfg["vocab_size"]
    return (matrix_item(cfg) * (row_matrices(cfg) + head
                                + experts_active * expert_matrices(cfg))
            + 4 * row_floats(cfg)
            + window_bytes(cfg, rows_live, rows_chosen))


def prefill_flops(cfg: dict, prompt_rows: float, expert_pairs: float,
                  index_pairs: float, chosen_pairs: float,
                  prompts: float) -> float:
    """Model FLOPs of a prefill's LIVE rows, a multiply and an add each,
    the prediction layer's walk of the prompt with them: every row
    through `row_matrices` and the routers; the held (token, expert)
    pairs the program counted (all sparse layers, the prediction layer's
    among them); in EVERY mixer the indexer's heads over every (query,
    key) pair under the causal mask (`index_pairs`, one layer's) and
    attention over the pairs a layer KEEPS (`chosen_pairs`: a query at t
    keeps min(t + 1, index_topk)), score and weighted sum; the head on
    TWO rows a prompt (the first token's and the first draft's). Not the
    bucket's padding, nor the pairs the flash kernel computes and masks,
    nor the zero channels it is handed."""
    d = cfg["hidden_size"]
    sparse = n_sparse(cfg) + n_predict(cfg)
    per_row = row_matrices(cfg) + sparse * d * cfg["n_routed_experts_scored"]
    heads = cfg["num_attention_heads"] * (
        cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"]
        + cfg["v_head_dim"])
    return (2.0 * per_row * prompt_rows
            + 2.0 * expert_matrices(cfg) * expert_pairs
            + 2.0 * n_mixers(cfg) * cfg["index_n_heads"]
            * cfg["index_head_dim"] * index_pairs
            + 2.0 * n_mixers(cfg) * heads * chosen_pairs
            + 2.0 * d * cfg["vocab_size"] * (1 + n_predict(cfg)) * prompts)


def window_events(ops):
    """[(start, end)] of the device events of a round's two-position
    kernels, told by their own names."""
    return [(s, s + d) for n, s, d, _ in ops
            if any(k in n for k in WINDOW_KERNELS)]


def rounds(spans, modules, intervals, program_spans):
    """[(seconds of `intervals` inside the round's program, the counts
    of its `decode.loop.dispatch` phase)] for every traced round
    (`jit_ptpu_round_*`) whose phase carries `round_positions`. Empty
    for a program that runs no round (the parent of the PR that added
    them)."""
    out = []
    for name, m0, md in modules:
        if ROUND not in name:
            continue
        counts = program_spans.step_of(spans["host"], m0)
        if counts is not None and "round_positions" in counts:
            out.append((_inside(intervals, m0, md) * 1e-9, counts))
    return out


def spec_rounds(run: dict):
    """[(accepted, proposed)] of the `decode.spec_round` spans (one a
    live slot a round) the traced run's recorder still holds."""
    return [(float(s.get("accepted", 0)), float(s.get("proposed", 0)))
            for s in run.get("spans") or ()
            if s.get("name") == "decode.spec_round" and s.get("proposed")]
