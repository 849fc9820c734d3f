"""symm_panel_cols.eval: output columns that one fetch of an S tile
serves in the SYMM kernel (``repro.symm.panel_cols``), per symm call of
the step walker (``repro.step.{symm,symm_r}``), in columns (layer:
kernels).

A program whose SYMM kernel fetches S once per output column block has
no such counter: nothing is read.
"""

from chipbench import spans


def read(rec):
    snap = spans.recorded(rec, "plan", "planner.call")
    if snap is None or "repro.symm.panel_cols" not in snap:
        return None
    calls = spans.count(snap, "step.symm") + spans.count(snap, "step.symm_r")
    if not calls:
        return None
    return snap["repro.symm.panel_cols"] / calls
