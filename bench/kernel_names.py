"""Every match of a device-trace event to a kernel of the program.

The paged decode kernel and the paged chunk-stats kernel are Pallas
calls.  A TPU profile names a device operation by its HLO text, and a
Pallas call's text carries neither kernel's function name
(``_paged_decode_kernel``, ``_paged_chunk_kernel``) nor an ``op_name``:
it reads ``%closed_call.N = ... custom-call(...),
custom_call_target="tpu_custom_call"``.  The engine's decode executable
(module ``jit_decode_fn``) holds one such call, the decode kernel, run
once per layer and step, and its chunk executable (``jit_chunk_fn``)
one, the chunk-stats kernel.  The other custom-calls of those modules
(``AllocateBuffer``, ``ConcatBitcast``) are XLA's own and do not match.
The reduction prefixes each operation's label with the module that ran
it, so an event matches a kernel when its label holds all of the
kernel's marks.  If a later change names the kernels, only this file
changes.
"""
from __future__ import annotations

from typing import Optional

PALLAS = 'custom_call_target="tpu_custom_call"'

KERNELS = {
    "decode": "jit_decode_fn",
    "chunk": "jit_chunk_fn",
}


def kernel_of(label: str) -> Optional[str]:
    """``"decode"``, ``"chunk"`` or None for a device operation's label:
    its module, name and string stats, joined."""
    if PALLAS not in label:
        return None
    for kind, module in KERNELS.items():
        if module in label:
            return kind
    return None
