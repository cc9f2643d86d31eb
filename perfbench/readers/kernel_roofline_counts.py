"""`kernel_roofline`, with the count of required work taken from
`perfbench/counts/<arch>.py` rather than from `archs/<arch>.py`: the file
in which a metric added after a family's own module keeps its count, since
a later PR adds files to the benchmark and edits none (`harness/cells.py`).
Same arguments, same reading; None where the trace holds no operation that
matches `op` (a program without the kernel, such as a parent tree's)."""
from perfbench.harness.cells import load_module


class _Counts:
    """What `kernel_roofline` asks of the cell: its count by name."""

    def __init__(self, cell):
        arch = cell.config["arch"]
        self._module = load_module(cell.root / "counts" / f"{arch}.py",
                                   f"perfbench_counts_{arch}")

    def count(self, name: str):
        return getattr(self._module, name)


def read(run, args):
    cell = run.get("cell")
    if cell is None:
        return None
    roofline = load_module(cell.root / "readers" / "kernel_roofline.py",
                           "perfbench_reader_kernel_roofline")
    return roofline.read(dict(run, cell=_Counts(cell)), args)
