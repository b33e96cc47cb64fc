"""Which device operations are the program's sweep kernel.

The kernel is the Mosaic custom call that `fcm_accumulate_pallas`
lowers to: an HLO ``custom-call`` with ``custom_call_target=
"tpu_custom_call"`` whose instruction is named after that function.
The name is held here, not read from the program, so that a change to
the program cannot move what the benchmark counts.
"""
from __future__ import annotations

from .trace import operand_rows, opcode, short_name

KERNEL = "fcm_accumulate_pallas"


def is_sweep(name: str) -> bool:
    return (short_name(name).split(".")[0] == KERNEL
            and opcode(name) == "custom-call"
            and 'custom_call_target="tpu_custom_call"' in name)


def chip_rows(run) -> int:
    """Rows of the data that each chip holds (unpadded)."""
    cfg = run.cell.config
    rows = int(run.record.get("rows") or cfg["rows"])
    return -(-rows // int(cfg["chips"]))


def full_sweeps(run) -> list:
    """Every kernel call over a chip's whole share of the data (the
    combiner's sweeps and the objective pass; the driver's calls on its
    sample and the reducer's on the summaries are smaller)."""
    n = chip_rows(run)
    out = []
    for ops in run.trace.devices.values():
        for op in ops:
            if is_sweep(op.name):
                shape = operand_rows(op)
                if shape is not None and shape[0] >= n:
                    out.append(op)
    return out
