"""Sorted rows the expert layer walked over the assignments it held, a
ratio (unit `x`, at least 1: the rows beyond the load are gathered, masked
and scattered like the live ones), over every step and MoE layer of the
timed window: the sum of the steps' own `moe_rows_walked` over the sum of
their `moe_assignments_held` (`facts["moe_rows_walked_by_step"]` and
`facts["moe_held_by_step"]`, (steps, MoE layers), kept on the device
through the window and read after it). A layer-step that walks a second
block counts with the rows it walked. None where the program's steps state
no rows walked (a tree from before the loop over live blocks) or hold
nothing."""
import numpy as np


def read(facts: dict, args: dict):
    rows, held = facts.get("moe_rows_walked_by_step"), facts.get("moe_held_by_step")
    if rows is None or held is None or not np.sum(held):
        return None
    return float(np.sum(rows) / np.sum(held))
