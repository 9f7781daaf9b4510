"""The program's names for the profiler: device layer scopes and host spans.

Both land on the profiler's one clock, beside the device operations:

* ``DEVICE_SCOPES`` are ``jax.named_scope`` names around the layers of a
  federated round (``fed/round.py``, ``fed/server.py``).  A scope lands in
  the ``op_name`` metadata of every HLO instruction traced under it
  (``jit(scan_segment)/while/body/round.solve/...``) and changes no generated
  code; a fusion takes its root instruction's ``op_name``.  Scopes are one
  level deep: no ``round.*`` scope opens inside another, so each device op
  belongs to at most one layer, and an op in none belongs to the segment loop.
* ``MODEL_SCOPES`` name a model layer inside a round scope
  (``.../round.local_train/.../ssm.scan/...``); the round's scope stays the
  first ``round.*`` component, so a model scope splits a layer's time and
  moves none between layers.
* ``HOST_SPANS`` are host phases, each written as a
  ``jax.profiler.TraceAnnotation`` named ``"repro." + name`` by ``span``:
  the serve engine's prefill, decode step and swap, the segment call, and the
  checkpoint save.  A dotted name is a child of the span it extends
  (``serve.step.wait`` runs inside ``serve.step``).

With no profiler running a span costs well under a microsecond and a scope
nothing at run time.
"""
from __future__ import annotations

import jax

__all__ = ["DEVICE_SCOPES", "MODEL_SCOPES", "HOST_SPANS", "span"]

DEVICE_SCOPES = (
    "round.solve",  # sampler.probabilities, the water-filling kernel included
    "round.draw",  # sampler.sample_from
    "round.select",  # estimator.client_weights and select_cohort's top_k
    "round.gather",  # the cohort's batch keys and batch gather
    "round.local_train",  # the cohort's local steps
    "round.aggregate",  # weighted delta sum, quantization, the params update
    "round.sampler_update",  # the feedback scatter and sampler.update
    "round.faults",  # availability, deadline and buffered-async steps
    "round.eval",  # the periodic accuracy cond
)

# Model-level device scopes: opened inside a layer of the round (the scan of
# a Mamba2 mixer runs inside ``round.local_train``), so they never take the
# place of the round's own scope in an op_name.
MODEL_SCOPES = (
    "ssm.scan",  # models/ssm.py: the chunked SSD and the decode-step state update
)

HOST_SPANS = (
    "serve.start",  # ServeEngine.start: prefill and first-token dispatch
    "serve.step",  # ServeEngine.step, with three children:
    "serve.step.prep",  # key split and the index / temperature transfers
    "serve.step.dispatch",  # the decode call
    "serve.step.wait",  # block_until_ready on the last token
    "serve.swap",  # ServeEngine.swap_params
    "train.place",  # a segment call's device_put to the canonical placement
    "train.dispatch",  # a segment call's jitted scan dispatch
    "train.ckpt_save",  # run_segmented's CheckpointManager.save
    "train.publish",  # run_segmented's publish hook
    "ckpt.fetch",  # save_checkpoint's device-to-host reads
    "ckpt.write",  # save_checkpoint's file writes
)
_HOST_SPANS = frozenset(HOST_SPANS)


def span(name: str, **ids) -> jax.profiler.TraceAnnotation:
    """The host span ``repro.<name>``.

    ``ids`` become the span's arguments in the trace.  Give only host-side
    integers (a batch count, a cache index, a call count): reading a device
    value here would make the host wait for the device."""
    if name not in _HOST_SPANS:
        raise ValueError(f"unknown host span {name!r}; obs.HOST_SPANS has {HOST_SPANS}")
    return jax.profiler.TraceAnnotation("repro." + name, **ids)
