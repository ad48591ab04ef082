"""Samples of steps completed inside the window / window seconds / chips.
The window ends when the last issued step has been waited for, so every
counted step is complete.  Host clock."""


def samples_per_second_per_chip(run: dict):
    if not run["steps"]:
        return None
    return run["samples"] / run["window_s"] / run["chips"]


def per_second_per_chip(run: dict, sample_unit: str):
    if run["sample_unit"] != sample_unit:
        return None
    return samples_per_second_per_chip(run)
