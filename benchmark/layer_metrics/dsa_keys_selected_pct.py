"""The (query, key) pairs the layers' selections keep over the causal pairs,
all selecting layers together: `sum_t min(t + 1, topk)` over `seq (seq + 1) /
2`, 43.75 at 8,192 rows and 2,048, and more by the keys tied at a threshold.
What the dense tiles under a mask of data compute beyond it is the waste
`mfu_pct` prices.  Source: the program's own counters (`dsa_keys_selected`,
`dsa_keys_causal` in the `intermediates` collection), read by a probe outside
the window."""

from benchmark.layer_metrics import _keye

probe = _keye.counters_probe


def read(run: dict):
    seen = run["probes"].get("dsa_keys_selected_pct")
    if not seen or not seen["counts"]:
        return None
    return 100.0 * sum(layer[0] for layer in seen["counts"]) \
        / sum(layer[1] for layer in seen["counts"])
