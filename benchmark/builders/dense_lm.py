"""A dense decoder LM trained through the program's normal path:
`models.TransformerLM` (flash attention on), `models.next_token_loss`,
`hvd.jax.build_train_step` on `data_parallel_mesh(devices)`, AdamW — the step
of the repository's LM example at the widths the configuration file gives.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import optax
from jax.sharding import NamedSharding, PartitionSpec as P

from benchmark import ops_count
from benchmark.builders import Built, collectives_expected, dtype_of
from benchmark.reference import compare, dense_lm as reference

AXIS = "hvd"


def build(config: dict, traffic: dict, devices, seed: int) -> Built:
    from horovod_tpu.jax.train import build_train_step
    from horovod_tpu.models import TransformerLM, next_token_loss
    from horovod_tpu.ops import flash_attention
    from horovod_tpu.ops.attention import _bwd_plan
    from horovod_tpu.parallel import data_parallel_mesh

    hidden, heads = config["hidden_size"], config["num_attention_heads"]
    layers, vocab = config["num_hidden_layers"], config["vocab_size"]
    seq, per_chip = traffic["sequence_length"], traffic["batch_per_chip"]
    dtype = dtype_of(config["compute_dtype"])
    model = TransformerLM(vocab_size=vocab, d_model=hidden, n_layers=layers,
                          n_heads=heads, d_ff=config["intermediate_size"],
                          dtype=dtype,
                          logits_dtype=dtype_of(config["logits_dtype"]),
                          use_flash=True)
    mesh = data_parallel_mesh(devices, axis_name=AXIS)
    replicated = NamedSharding(mesh, P())
    if config["optimizer"]["name"] != "adamw":
        raise ValueError(f"dense_lm builds AdamW, not {config['optimizer']}")
    tx = optax.adamw(config["optimizer"]["learning_rate"])

    def loss_fn(params, batch):
        inputs, targets = batch
        return next_token_loss(model.apply({"params": params}, inputs),
                               targets)

    step = build_train_step(loss_fn, tx, mesh, axis_name=AXIS,
                            batch_spec=(P(AXIS), P(AXIS)))

    def init_state():
        # Weights do not depend on the sequence length: trace the
        # initialiser at a short one.  One jitted call, on the device.
        def init(key):
            params = model.init(key, jnp.zeros((1, 128), jnp.int32))["params"]
            return params, tx.init(params)

        return jax.jit(init, out_shardings=replicated)(
            jax.random.PRNGKey(seed))

    def make_batch(fields):
        tokens = fields["tokens"]
        return tokens[:, :-1], tokens[:, 1:]

    def reference_checks(state, pool):
        device = devices[0]
        params = compare.first_device_copy(state[0])
        inputs, targets = compare.first_device_copy(pool[0])
        plan = traffic["reference_check"]
        spec = (P(AXIS), P(AXIS))
        if plan.get("forward_only"):
            # The whole sequence forward (the reference cannot hold its
            # gradients at this length) ...
            system = compare.system_on_one_device(loss_fn, spec, False,
                                                  device, AXIS, grad=False)
            batch = (inputs[:1], targets[:1])
            rows = compare.forward_loss(system, reference.loss, params,
                                        batch, batch, reference.LOSS_RTOL)
            # ... and the attention kernels' gradients at that length on a
            # few heads of the model's size.
            shape = (1, plan["flash_heads"], seq, hidden // heads)
            rows += compare.kernel_against(
                lambda q, k, v: flash_attention(q, k, v, causal=True),
                reference.attention, shape, dtype, seed,
                reference.FLASH_FWD_ATOL, reference.FLASH_GRAD_RTOL, "flash_")
            return rows
        n = plan["grad_batch"]
        system = compare.system_on_one_device(loss_fn, spec, False, device,
                                              AXIS)
        batch = (inputs[:n], targets[:n])
        return compare.loss_and_gradients(
            system, reference.loss, params, batch, batch,
            reference.LOSS_RTOL, reference.GRAD_RTOL,
            reference.GRAD_NORM_RTOL)

    # Forward and backward attention must be Pallas kernels in the compiled
    # step, as many as the backward plan says (combined: 2 a layer, split: 3),
    # and the blockwise scan (a while loop) must not be there.
    mode = _bwd_plan(seq, hidden // heads, 1024, 1024, per_chip * heads)[0]
    calls = {"combined": 2, "split": 3}[mode] * layers
    no_more, at_least_one = collectives_expected(devices)
    ops = ops_count.dense_lm_train_ops_per_token(
        hidden, config["intermediate_size"], layers, vocab, seq)
    return Built(
        mesh=mesh, step=step, init_state=init_state,
        fields=[{"name": "tokens", "shape": [seq + 1], "dtype": "int32",
                 "high": vocab}],
        make_batch=make_batch,
        samples_per_step=per_chip * len(devices) * seq, sample_unit="token",
        ops_per_sample=ops,
        kernels={"flash": {
            "ops": ops_count.flash_kernel_ops_per_token(seq, hidden, layers),
            "bytes": ops_count.flash_kernel_bytes_per_token(
                hidden, layers, jnp.dtype(dtype).itemsize)}},
        program_exactly={"tpu_custom_call": calls, "while": 0, **no_more},
        program_at_least_one=at_least_one,
        plain_loss_fn=loss_fn, optimizer=tx, has_aux=False,
        reference_checks=reference_checks,
        notes={"flash_backward": mode})
