"""``mfu.serve``: the operations the window's tokens needed, per second,
over the chip's bf16 peak, in %.

Every prompt token of an admission and every decoded token passes through
all layers (the configuration's reference counts them in ``work(cfg)``:
for granite the projections and the FFN's stored tiles only); attention
counts the keys each token attends; the unembedding counts once per logits
row (a prefill produces one).  The token counts are the client's, over the window."""


def read(run):
    c = run.outcome.counters
    if not c.get("window_s") or not c.get("tokens_through_layers"):
        return None
    per_token, per_key, per_row = c["flops"]
    ops = (per_token * c["tokens_through_layers"] + per_key * c["keys_attended"]
           + per_row * c["logits_rows"])
    return 100.0 * ops / c["window_s"] / run.peaks["bf16_flops_per_s"]
