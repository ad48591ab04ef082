"""How much slower the library's step is than the benchmark's own plain
jax.jit(value_and_grad + optax update) step on the same loss, weights and
batches: 100 * (median library step / median plain step - 1), each over
`steps` steps that are waited for one by one, the two taken in turns so that
drift hits both.  One-chip cells only: the plain step has no mesh."""

import statistics


def probe(context: dict):
    jax, built = context["jax"], context["built"]
    plain = context["plain_step"](jax, built.plain_loss_fn, built.optimizer,
                                  built.has_aux)
    state, pool = context["state"], context["pool"]
    # Compile and warm the plain step outside the timed steps.
    state, _, _ = context["blocked_steps"](jax, plain, state, pool, 3)
    library_s, plain_s = [], []
    for i in range(context["steps"]):
        state, _, s = context["blocked_steps"](jax, built.step, state, pool,
                                               1, 2 * i)
        library_s += s
        state, _, s = context["blocked_steps"](jax, plain, state, pool, 1,
                                               2 * i + 1)
        plain_s += s
    context["state"] = state
    out = {"library_step_s": statistics.median(library_s),
           "plain_step_s": statistics.median(plain_s)}
    context["note"](framework_overhead_probe=out)
    return out


def read(run: dict):
    probe = run["probes"].get("framework_overhead_pct")
    if not probe:
        return None
    return 100.0 * (probe["library_step_s"] / probe["plain_step_s"] - 1.0)
