"""Model FLOP utilisation of the serving step, percent: the model
operations of the tokens the engine processed in the traced window over
(window × chips × bf16 peak).

Operations of one token, from the configuration's shapes: 2 per weight
of every matmul (attention, MLP, LM head, the memory's query, write word,
gates and read-out projections); attention over its live context,
4·H·D per attended position per layer; and per memory group the read's
sweep of N rows, 2·H·W + 2·W a row. Lanes that hold no request do no
useful work and are not counted.
"""


def token_flops(m, mem):
    d, H, Hkv, D = m["d_model"], m["num_heads"], m["num_kv_heads"], \
        m["head_dim"]
    groups = max(1, m["num_layers"] // mem["every_n_layers"])
    M, W = mem["num_heads"], mem["word_size"]
    per_layer = d * (H + 2 * Hkv) * D + H * D * d + 3 * d * m["d_ff"]
    per_group = 2 * d * M * W + d * M * 3 + M * W * d
    weights = m["num_layers"] * per_layer + groups * per_group \
        + d * m["vocab_size"]
    sweep = groups * mem["num_slots"] * (2 * M * W + 2 * W)
    return 2.0 * weights + sweep, 4.0 * H * D * m["num_layers"]


def read(trace, window, cell):
    if window["lane_steps"] == 0 or window["seconds"] <= 0:
        return None
    fixed, per_ctx = token_flops(cell["model"], cell["memory"])
    flops = fixed * window["lane_steps"] + per_ctx * window["context"]
    peak = cell["peaks"]["bf16_flops_per_s"] * cell["chips"]
    return 100.0 * flops / (window["seconds"] * peak)
