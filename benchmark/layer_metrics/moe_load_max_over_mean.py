"""``moe_load_max_over_mean`` (ratio, program counter): the routes
demanded of the busiest held expert over the mean of the held experts,
in the program's last step, all layers together: the gauges
``hvd_tpu_moe_expert_load{expert=}`` that ``parallel/moe.py``
``record_held_stats`` sets from inside the step. 1 is a balanced router;
the grouped matmuls' cost follows the sum, the exchange of a deployment
the maximum. ``None`` for a program that has no such gauge. Layer:
expert layer. Moves ``train_tokens_per_s``."""


def read(record):
    import horovod_tpu as hvd

    samples = hvd.metrics().get("hvd_tpu_moe_expert_load", {}) \
        .get("samples", [])
    loads = [float(s["value"]) for s in samples]
    if not loads or not sum(loads):
        return None
    return max(loads) / (sum(loads) / len(loads))
