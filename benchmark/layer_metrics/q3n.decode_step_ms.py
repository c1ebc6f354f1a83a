"""Mean host milliseconds of a boundary in which no prefill row rode
(``gen_prefill_rows_total`` did not move): one decode program over the
slots, the fetch of its tokens and the engine's bookkeeping. Where the
window holds fewer than eight such boundaries (the turns keep a window
riding in nearly every one), the intercept of the least-squares line of
the boundaries' seconds on their riding rows: what a boundary would take
with none."""

import numpy as np

from benchmark import generate_stats, q3n_stats


def read(ctx):
    quiet = q3n_stats.decode_only(ctx)
    if len(quiet) >= 8:
        return generate_stats.mean_ms(quiet)
    every = q3n_stats.window(ctx)
    rows = np.asarray([s["ride_rows"] for s in every], np.float64)
    if len(every) < 8 or rows.max() - rows.min() < 32:
        return generate_stats.mean_ms(quiet)
    seconds = np.asarray([s["seconds"] for s in every], np.float64)
    slope, intercept = np.polyfit(rows, seconds, 1)
    return 1e3 * float(intercept) if intercept > 0 else None
