"""bind + H2D: the share of the mapped program calls whose lanes the device
already held stacked: 100 x the sum of `stacks_kept` ÷ the sum of
`stacks_kept` and `stacks_built` over the program's `tidb:exec.inputs` spans
of the traced window. A batch cop task says on its `inputs` span how many of
its mapped calls read only `(m, rows)` lane stacks kept on the device from
before (`stacks_kept`) and how many built at least one (`stacks_built`); a
task without a mapped call says nothing. A span without the stats (a program
from before them) counts as nothing: such a program reads nothing here. Every
stack of a static cell is built in the warm-up, so it reads close to 100; a
cell whose writes change a batch's regions reads the re-builds."""
from harness.program_spans import of_run

UNIT = "%"


def read(ctx):
    spans = of_run(ctx)
    if spans is None:
        return None
    inputs = spans.inside("exec.inputs", *ctx.trace_window)
    kept = sum(int(s.stats.get("stacks_kept", 0)) for s in inputs)
    total = kept + sum(int(s.stats.get("stacks_built", 0)) for s in inputs)
    if not total:
        return None
    return 100.0 * kept / total
