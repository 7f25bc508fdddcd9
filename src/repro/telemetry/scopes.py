"""Device scopes: which part of the program each compiled op came from.

The device engine's segment wraps each part of the tick in
``jax.named_scope`` (``cohort.client_block``, ``cohort.deliver``, ...),
and each fused-kernel wrapper in a scope named after its kernel
(``tick_deliver``, ...).  The scopes change no op and no fusion: they
only land in the compiled module's ``metadata={op_name="..."}`` of each
instruction, fusions included.  ``op_scopes`` reads them back, so a
profiler trace, whose device events are named by instruction, can put
each op's time under the layer that caused it.
"""
from __future__ import annotations

import re
from typing import Dict

#: the segment's own scopes, outermost first (``repro.cohort.device``)
SEGMENT_SCOPES = (
    "cohort.segment", "cohort.overflow_pop", "cohort.server_apply",
    "cohort.cascade", "cohort.deliver", "cohort.client_block",
    "cohort.scenario", "cohort.complete", "cohort.predict_block",
)
#: the fused-kernel wrappers' scopes (``repro.kernels``): the kernel
#: call with the wrapper's pads, slices and copies around it (and, for
#: the client block, its sample-row gather)
KERNEL_SCOPES = ("tick_deliver", "bucket_apply", "tick_scatter",
                 "cohort_clip_noise", "client_block_sgd")
#: the client-axis all-reduces of a sharded fleet
ALLREDUCE_SCOPE = "cohort.allreduce"
#: a ``layer_types`` model's parts inside the client block
#: (``repro.models.transformer``; ``model.lora`` nests in the others)
MODEL_SCOPES = ("model.mamba", "model.attention", "model.mlp",
                "model.lora", "model.loss")
DEVICE_SCOPES = (SEGMENT_SCOPES + KERNEL_SCOPES + (ALLREDUCE_SCOPE,)
                 + MODEL_SCOPES)

_INSTR_RE = re.compile(
    r"^\s*(?:ROOT\s+)?%?([^\s=]+)\s*=\s.*?\bmetadata=\{[^}]*?"
    r'\bop_name="([^"]*)"', re.M)


_WRAP_RE = re.compile(r"^(?:[A-Za-z_]+\()+(.*?)\)*$")


def scope_path(op_name: str) -> str:
    """The device scopes in an ``op_name``, outermost first, joined by
    ``/`` (``"cohort.segment/cohort.complete/tick_scatter"``); ``""``
    when the op lies under none.  A scope repeated at once (a Pallas
    kernel's own name under its wrapper's scope) is kept once.  A scope
    opened at the top of a differentiated function reads as
    ``transpose(jvp(model.loss))``: the wrappers are taken off."""
    out = []
    for c in op_name.split("/"):
        m = _WRAP_RE.match(c)
        c = m.group(1) if m else c
        if c in DEVICE_SCOPES and (not out or out[-1] != c):
            out.append(c)
    return "/".join(out)


def op_scopes(hlo_text: str) -> Dict[str, str]:
    """{instruction name: scope path} of every instruction of a compiled
    module's text (``Compiled.as_text()``) that carries ``op_name``
    metadata under at least one device scope.  Instruction names are
    what a device trace names its op events by (``fusion.12``)."""
    out: Dict[str, str] = {}
    for name, op_name in _INSTR_RE.findall(hlo_text):
        path = scope_path(op_name)
        if path:
            out[name] = path
    return out
