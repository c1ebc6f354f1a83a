"""Mean context a decoding row attends, in tokens, over the window: the
latent positions the decoding rows read (``latent_decode_positions_total``,
a row at position p reads p + 1, summed over the latent layers) over the
decoding rows (``kda_step_rows_total``, summed over the KDA layers), each
a layer."""

from benchmark import kimi_stats


def read(ctx):
    stats = [s for s in kimi_stats.window(ctx) if "latent_positions" in s]
    rows = sum(s["kda_step_rows"] for s in stats)
    if rows <= 0:
        return None
    cfg = ctx["cfg"]
    layers = int(cfg["num_hidden_layers"])
    mla = len([i for i in cfg["linear_attn_config"]["full_attn_layers"]
               if int(i) <= layers])
    return sum(s["latent_positions"] for s in stats) / mla \
        / (rows / (layers - mla))
