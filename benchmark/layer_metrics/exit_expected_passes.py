"""How deep the exit gate sends a token: the mean over positions of `sum_t t
p_t`, `p` the exit distribution over the passes of a looped model, for one
batch of the pool with the weights as the window left them — a GAUGE between 1
and the number of passes (2.34 to 2.75 of 4 on the chip over PR 54's seeds: it
swings a fifth from seed to seed), which a change of the gate's arithmetic or
of the seeded start moves.  No end-to-end metric follows it: in training every
pass runs whatever the gate says, and `BENCHMARK.json`'s `moves` and `better`
are there because the form wants them (what early exit would save at serving
is what lies under the number of passes, and nothing here serves).  Source:
the program's own counter (`exit_gate_logits` in the `intermediates`
collection, read by `models.record_exit_distribution`), by a probe outside the
window.  A builder with no looped model gives None."""

PROBE = "exit_expected_passes"


def probe(context: dict):
    record_of = getattr(context["built"], "exit_distribution", None)
    if record_of is None:
        return None
    from benchmark.reference import compare

    record = record_of(compare.first_device_copy(context["state"][0]),
                       compare.first_device_copy(context["pool"][0]))
    context["note"](exit_distribution_probe=record)
    return record


def read(run: dict):
    record = run["probes"].get(PROBE)
    return record["expected_passes"] if record else None
