"""50th percentile of every gap between two consecutive tokens of one
request, over the gaps whose later token arrived in the window."""
import loadgen


def read(ctx):
    gaps = loadgen.window_gaps(ctx["sent"], ctx["T"], ctx["seconds"])
    return loadgen.percentile(gaps, 50) * 1e3 if gaps else None
