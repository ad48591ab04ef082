"""Device memory of the step that ran, by the compiler's own
memory_analysis(): arguments + outputs + temporaries - aliased, per chip, in
GB (1e9).  The runtime's peak_bytes_in_use leaves temporaries out."""


def read(run: dict):
    return run["step_memory_bytes"] / 1e9
