"""Operations and bytes of a MiMo-V2-family configuration (sliding layers
with a learned sink beside full layers of FEWER key/value heads, query
and key heads wider than value heads, routed experts with no shared
one), from the configuration's keys alone: what the `*_swa.serve`
readers and `decode_attn_uneven_roofline.serve` divide by the peaks.
The WORK is counted (live rows, held and chosen experts, matrices
read), never the implementation: the same whatever kernel or lax path
computes it. `lib/moe_cost.py` counts from Laguna's keys (`layer_types`,
`num_experts`, one head geometry) and is an accepted file. Kept with the
benchmark, apart from the program (`paddle_tpu` computes none of
this)."""
from __future__ import annotations

from .ling_cost import _inside

ITEM = 4  # float32 weights, slabs and rings

# the scope a full layer's decode attention runs under, kernel or lax
# path: a Mosaic call's event is named after it, a lax path's events
# carry it in their map
DECODE_ATTN = "ptpu.decode_attn_uneven"


def is_family(cfg: dict) -> bool:
    return ("hybrid_layer_pattern" in cfg
            and "swa_num_key_value_heads" in cfg)


def depth(cfg: dict) -> int:
    return int(cfg["num_hidden_layers"])


def kinds(cfg: dict):
    """"full" | "sliding" layer by layer."""
    return ["sliding" if p else "full"
            for p in cfg["hybrid_layer_pattern"][:depth(cfg)]]


def layers_of(cfg: dict, kind: str):
    return [i for i, k in enumerate(kinds(cfg)) if k == kind]


def sparse_layers(cfg: dict):
    return [i for i in range(depth(cfg)) if cfg["moe_layer_freq"][i]]


def kv_heads(cfg: dict, kind: str) -> int:
    return int(cfg["swa_num_key_value_heads"] if kind == "sliding"
               else cfg["num_key_value_heads"])


def attn_params(cfg: dict, kind: str) -> int:
    """One attention mixer: W_q and W_o at the query heads' widths, W_k
    and W_v at the kind's key/value heads', a sliding layer's sinks
    (89.13 M full, 94.37 M sliding at the published widths)."""
    d, h = cfg["hidden_size"], cfg["num_attention_heads"]
    dk, dv, hkv = cfg["head_dim"], cfg["v_head_dim"], kv_heads(cfg, kind)
    sinks = h if kind == "sliding" and cfg["add_swa_attention_sink_bias"] \
        else 0
    return d * h * dk + d * hkv * (dk + dv) + h * dv * d + sinks


def expert_params(cfg: dict) -> int:
    """ONE routed expert: gate, up and down (25.17 M = 100.7 MB)."""
    return 3 * cfg["hidden_size"] * cfg["moe_intermediate_size"]


def row_params(cfg: dict) -> int:
    """Parameters every row passes through, all layers (the routed
    experts and the head apart): the mixers, the dense layers' MLP, the
    sparse layers' router with its selection bias."""
    d = cfg["hidden_size"]
    total = sum(attn_params(cfg, k) for k in kinds(cfg))
    for i in range(depth(cfg)):
        if cfg["moe_layer_freq"][i]:
            total += (d + 1) * cfg["n_routed_experts_scored"]
        else:
            total += 3 * d * cfg["intermediate_size"]
    return total


def dense_params(cfg: dict) -> int:
    """What a decode step reads whatever it routes: `row_params`, two
    gains a layer, the final gain and the head's own matrix (the table's
    gathered rows are not counted)."""
    d = cfg["hidden_size"]
    return row_params(cfg) + 2 * d * depth(cfg) + d + d * cfg["vocab_size"]


def kv_row_bytes(cfg: dict) -> int:
    """Bytes of K and V one attended position costs a step, every full
    layer's: heads x (192 + 128) floats (10,240 at two layers of 4)."""
    return (len(layers_of(cfg, "full")) * kv_heads(cfg, "full")
            * (cfg["head_dim"] + cfg["v_head_dim"]) * ITEM)


def ring_row_bytes(cfg: dict) -> int:
    """The same for one row of every sliding layer's rings (51,200 at
    five layers of 8 heads)."""
    return (len(layers_of(cfg, "sliding")) * kv_heads(cfg, "sliding")
            * (cfg["head_dim"] + cfg["v_head_dim"]) * ITEM)


def step_bytes(cfg: dict, experts_active: float, attended: float,
               ring_rows: float) -> float:
    """Bytes one decode step HAS to move: the dense weights, the held
    (layer, expert) that received a pair, once each, the live rows of
    the full layers' slabs and of the sliding layers' rings."""
    return (ITEM * (dense_params(cfg) + experts_active * expert_params(cfg))
            + attended * kv_row_bytes(cfg) + ring_rows * ring_row_bytes(cfg))


def prefill_flops(cfg: dict, prompt_rows: float, expert_pairs: float,
                  attn_pairs: float, window_pairs: float,
                  prompts: float) -> float:
    """Model FLOPs of a prefill's LIVE rows, a multiply and an add each:
    every row through `row_params`; the held (token, expert) pairs the
    program counted; attention over the (query, key) pairs of the live
    rows, counted once, score (192 channels) and weighted sum (128),
    every query head: `attn_pairs` inside the causal triangle a full
    layer, `window_pairs` inside the window a sliding one; the head on
    one row a prompt. Not the bucket's padding, not the keys repeated
    for the query heads, not the zero channels q and k are padded
    with."""
    d, h = cfg["hidden_size"], cfg["num_attention_heads"]
    per_pair = 2.0 * h * (cfg["head_dim"] + cfg["v_head_dim"])
    return (2.0 * row_params(cfg) * prompt_rows
            + 2.0 * expert_params(cfg) * expert_pairs
            + per_pair * (len(layers_of(cfg, "full")) * attn_pairs
                          + len(layers_of(cfg, "sliding")) * window_pairs)
            + 2.0 * d * cfg["vocab_size"] * prompts)


def decode_steps(spans, modules, intervals, program_spans):
    """[(seconds of `intervals` inside the step's program, the counts of
    its `decode.loop.dispatch` phase)] for every traced decode step whose
    phase carries `ring_rows` beside `attended`."""
    out = []
    for name, m0, md in modules:
        if "ptpu_decode_" not in name:
            continue
        counts = program_spans.step_of(spans["host"], m0)
        if counts is not None and "ring_rows" in counts \
                and "attended" in counts:
            out.append((_inside(intervals, m0, md) * 1e-9, counts))
    return out


def admissions(spans, modules, intervals, program_spans):
    """[(seconds of `intervals` inside the prefill's program, the counts
    of the admission's `decode.loop.scatter` phase, the first that opens
    after the program has started)] for every traced prefill whose phase
    carries `window_pairs` beside `attn_pairs`."""
    scatter = program_spans.LOOP + "scatter"
    scatters = [(s, c) for name, s, _, c, _ in spans["host"]
                if name == scatter and "window_pairs" in c
                and "attn_pairs" in c]
    out = []
    for name, m0, md in sorted(modules, key=lambda m: m[1]):
        if "ptpu_prefill_" not in name:
            continue
        after = [c for s, c in scatters if s >= m0]
        if after:  # else the session ended before its scatter opened
            out.append((_inside(intervals, m0, md) * 1e-9, after[0]))
    return out


def scope_events(run, scope: str, prefix: str, scope_time):
    """[(start, end)] of the first chip's events inside the programs
    named `prefix`* that run under `scope`: a Mosaic call of that name,
    or an event whose own scopes, fused members or users carry it
    (`lib/scope_time.py`'s join: the lax path's events). None where no
    such program has a scoped map."""
    found = scope_time.of_run(run)
    out, seen = [], False
    if found:
        for program, _, _, evs, m, _ in found[1]:
            if not (program.startswith(prefix) and m and m["scoped"]):
                continue
            seen = True
            for e in evs:
                entry = m["ops"].get(e[0])
                names = [e[0]]
                if entry is not None:
                    names += (list(entry["scope"]) + list(entry["members"])
                              + list(entry.get("users", ())))
                if any(scope in n for n in names):
                    out.append((e[1], e[1] + e[2]))
    return out if seen else None


def _layer_marks(cfg: dict, kind: str):
    """What names a `kind` layer's mixer in a scope's anchor or a
    weight's name: `lm.l<i>.attention.`, i a layer of that kind."""
    return tuple(".l%d.attention." % i for i in layers_of(cfg, kind))


def of_part(cfg: dict, which: str):
    """`scope_time.select_s`'s predicate: the events of the full layers'
    mixers ("full"), the sliding layers' ("window") or the expert
    layers' ("experts"), told by a scope, a fused member, the scope
    their result goes to or a weight they read: a kind's own kernels and
    scopes (`ptpu.flash_fwd`, `ptpu.decode_attn_uneven` and the slab's
    append are the full layers'; `ptpu.attn_window`, `ptpu.decode_attn_
    ring`, `ptpu.ring_*` the sliding layers'), or a parameter of a layer
    of that kind. An elementwise event between two mixers, anchored at a
    temporary's name (a rotation, a reshape), belongs to none: the
    shares are lower bounds."""
    marks = {
        "full": ("ptpu.flash_fwd", "ptpu.decode_attn_uneven",
                 "fl.decode_attention_uneven", "fl.cache_append:")
        + _layer_marks(cfg, "full"),
        "window": ("ptpu.attn_window", "ptpu.decode_attn_ring",
                   "ptpu.ring_append", "ptpu.ring_pack",
                   "fl.decode_attn_ring", "fl.ring_append", "fl.ring_pack")
        + _layer_marks(cfg, "sliding"),
        "experts": (".moe.", "ptpu.moe_", "fl.moe_"),
    }[which]

    def want(entry, m):
        if entry is None:
            return False
        names = (list(entry["scope"]) + list(entry["members"])
                 + list(entry.get("users", ())) + list(entry["reads"]))
        return any(mark in n for n in names for mark in marks)

    return want
