#!/usr/bin/env python3
"""A run of one cell with the timed path broken underneath, or with keys
of the cell's file overridden: what shows that ``correct`` can come out
false, on the CPU in the tests and on the chip by hand.

    python3 tests/benchmark/faulty_run.py [--fault <name>] [--set key=<json> ...] \\
        --workload <cell> --seed <n> --seconds <s> --trace <0|1> [--rehearse-cpu]

Everything but ``--fault`` and ``--set`` goes to ``benchmark/run.py``
unchanged. The faults are put where the system's step is built and fed,
around ``hvd.DistributedOptimizer`` and ``hvd.infeed_pipeline``, and the
reference is not touched:

``sum_not_mean``       the gradients are summed over the chips, not
                       averaged: the missing 1/n
``module_left_out``    one top-level module of the parameters (the middle
                       one by name) gets no update
``scaled_gradients``   every gradient is 1.05 times what it should be
``learning_rate_off``  every update is 1.05 times what it should be: a
                       learning rate 5% off
``row_left_out``       the last row of every batch the step is fed is a
                       copy of its first: a part of the batch left out

``--set check_steps=1`` runs a cell against one reference step
(``first_step``) where its file has two of a trainer's, without a copy of
the file; ``--set chips=2`` a data-parallel cell on two (virtual) devices.
"""

import argparse
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

FAULTS = ("none", "sum_not_mean", "module_left_out", "scaled_gradients",
          "learning_rate_off", "row_left_out")


def faulty_optimizer(real, fault):
    """``hvd.DistributedOptimizer`` with ``fault`` in what it returns."""
    import jax
    import jax.numpy as jnp
    import optax

    import horovod_tpu as hvd

    def make(optimizer, **kwargs):
        if fault == "sum_not_mean":
            kwargs["op"] = hvd.Sum
        tx = real(optimizer, **kwargs)

        def update(grads, state, params=None):
            if fault == "scaled_gradients":
                grads = jax.tree.map(lambda g: 1.05 * g, grads)
            updates, state = tx.update(grads, state, params)
            if fault == "learning_rate_off":
                updates = jax.tree.map(lambda u: 1.05 * u, updates)
            if fault == "module_left_out":
                name = sorted(updates)[len(updates) // 2]
                updates = {**updates, name: jax.tree.map(
                    jnp.zeros_like, updates[name])}
            return updates, state

        return optax.GradientTransformation(tx.init, update)

    return make


def faulty_infeed(real):
    """``hvd.infeed_pipeline`` fed batches whose last row is their first."""
    import numpy as np

    def first_row_twice(batch):
        return {k: np.concatenate([v[:-1], v[:1]]) for k, v in batch.items()}

    return lambda batches, **kwargs: real(map(first_row_twice, batches),
                                          **kwargs)


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--fault", choices=FAULTS, default="none")
    parser.add_argument("--set", action="append", default=[],
                        metavar="KEY=JSON", dest="overrides")
    args, rest = parser.parse_known_args(argv)
    overrides = {k: json.loads(v) for k, v in
                 (item.split("=", 1) for item in args.overrides)}

    from benchmark import run as harness
    from benchmark.catalog import Catalog

    read_cell = Catalog.cell
    Catalog.cell = lambda self, name: {**read_cell(self, name), **overrides}
    if args.fault != "none":
        # JAX is imported here at the earliest, after ``run.py`` would have
        # set a rehearsal's environment: do that first.
        if "--rehearse-cpu" in rest:
            workload = rest[rest.index("--workload") + 1]
            harness._rehearsal_environment(
                Catalog(ROOT).cell(workload)["chips"])
        import horovod_tpu as hvd

        if args.fault == "row_left_out":
            hvd.infeed_pipeline = faulty_infeed(hvd.infeed_pipeline)
        else:
            hvd.DistributedOptimizer = faulty_optimizer(
                hvd.DistributedOptimizer, args.fault)
    return harness.main(rest)


if __name__ == "__main__":
    sys.exit(main())
