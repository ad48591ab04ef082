"""The (token, head) steps of the Gated DeltaNet layers whose step `beta =
beta_scale sigmoid(b)` is over 1, all layers together, over all their steps:
that the steps past 1 — where the transition `I - beta k k^T` has a negative
eigenvalue, what `linear_allow_neg_eigval` allows and the delta rule's solve
has to hold — are there in what the cell runs (about half on seeded weights).
Source: the program's own counters (`gdn_beta_over_one`, `gdn_beta_steps` in
the `intermediates` collection), read by a probe outside the window."""

from benchmark.layer_metrics import _olmohybrid

probe = _olmohybrid.steps_probe


def read(run: dict):
    seen = run["probes"].get("gdn_beta_over_one_pct")
    if not seen or not sum(seen["steps"]):
        return None
    return 100.0 * sum(seen["over_one"]) / sum(seen["steps"])
