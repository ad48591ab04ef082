"""The multi-token-prediction module's loss over the main head's, `L_mtp /
L_main`, of one forward pass on the first batch of the pool with the weights
as the window left them: near 1 on seeded weights (both are about the
logarithm of the vocabulary slice), and a module that stopped training, or
reads the wrong shift, reads off it.  Source: the program's own counter
(`mtp_losses` in the `intermediates` collection), read by a probe outside the
window."""

from benchmark.layer_metrics import _joyai

probe = _joyai.losses_probe


def read(run: dict):
    seen = run["probes"].get("mtp_loss_over_main")
    if not seen or not seen["modules"] or not seen["main"]:
        return None
    return sum(seen["modules"]) / len(seen["modules"]) / seen["main"]
