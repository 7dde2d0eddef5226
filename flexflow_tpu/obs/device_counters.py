"""Counters and gauges a compiled step keeps ON THE DEVICE, in the
model's state, and the host publishes into ``METRICS`` only where it
already waits for the device (``fit``'s epoch-end readback of the loss):
a step gains no host sync for being counted.

An op declares such a value in its ``state_specs`` under the name
``obs/<metric>`` (state key ``<op>/obs/<metric>``) and adds to it in its
forward.  An integer value is a COUNTER: the state holds the running
total since the parameters were initialised, every op that declares the
metric adds into the one ``METRICS`` counter, and a publish increments
it by what was added since the last one (modulo 2^32, so a total that
wraps still counts right).  A float value is a GAUGE: the last step's
reading, averaged over the ops that declare it.
"""

from __future__ import annotations

from typing import Dict

import numpy as np

from flexflow_tpu.obs.metrics import METRICS

MARK = "/obs/"


def metric_of(state_key: str):
    """``layer1_dispatch/obs/moe.assignments`` -> ``moe.assignments``;
    None for a state key that is no device counter."""
    _, mark, metric = state_key.partition(MARK)
    return metric if mark else None


def publish(state: Dict[str, object], seen: Dict[str, int]) -> None:
    """Read every ``*/obs/*`` value of ``state`` (one transfer) and put
    it into ``METRICS``.  ``seen`` holds the totals already published
    from this state (the model keeps it; empty after a fresh
    ``init_params``) and is updated."""
    import jax

    keys = [k for k in state if MARK in k]
    if not keys:
        return
    values = jax.device_get([state[k] for k in keys])
    gauges: Dict[str, list] = {}
    for key, value in zip(keys, values):
        value = np.asarray(value)
        metric = metric_of(key)
        if np.issubdtype(value.dtype, np.integer):
            total = int(value) & 0xFFFFFFFF
            METRICS.counter(metric).inc((total - seen.get(key, 0)) & 0xFFFFFFFF)
            seen[key] = total
        else:
            gauges.setdefault(metric, []).append(float(value))
    for metric, readings in gauges.items():
        METRICS.gauge(metric).set(sum(readings) / len(readings))

