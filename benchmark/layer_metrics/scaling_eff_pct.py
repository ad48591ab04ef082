"""Samples per second per chip on all the cell's chips over samples per
second of the same per-chip shape on a one-device mesh (the first device),
`steps` waited-for steps each, in the same process.  Falls when compute gets
faster and the exchange does not: a per-layer metric, never a bound."""

import statistics


def probe(context: dict):
    jax, built, devices = context["jax"], context["built"], context["devices"]
    blocked = context["blocked_steps"]
    state, _, many_s = blocked(jax, built.step, context["state"],
                               context["pool"], context["steps"])
    # Make room on the first device before the second program is built.
    del state
    context["state"] = context["pool"] = None
    one, pool = context["build"](devices[:1])
    state = one.init_state()
    state, _, _ = blocked(jax, one.step, state, pool, 3)
    state, _, one_s = blocked(jax, one.step, state, pool, context["steps"])
    out = {"all_chips_step_s": statistics.median(many_s),
           "one_chip_step_s": statistics.median(one_s),
           "all_chips_samples_per_chip": built.samples_per_step
           / len(devices), "one_chip_samples": one.samples_per_step}
    context["note"](scaling_probe=out)
    return out


def read(run: dict):
    probe = run["probes"].get("scaling_eff_pct")
    if not probe:
        return None
    many = probe["all_chips_samples_per_chip"] / probe["all_chips_step_s"]
    one = probe["one_chip_samples"] / probe["one_chip_step_s"]
    return 100.0 * many / one
