"""Device time of the operations whose scope path holds `hvd_optimizer`
(`build_train_step`: `DistributedOptimizer`'s exchange, the optax update,
`apply_updates`) over the time of all operations.  A fusion has one path,
its root's: a weight-gradient matmul whose epilogue is the update counts
here whole.  Source: device trace, sorted by the compiled step's op_name."""

from benchmark.layer_metrics import _program

probe = _program.op_names_probe


def read(run: dict):
    return _program.phase_share_pct(run, "optimizer")
