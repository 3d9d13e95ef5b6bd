"""Share of the traced window in which no operation ran on the device, in %
(``tracecalc.idle_percent``)."""
import tracecalc


def read(run):
    return tracecalc.idle_percent(run.trace)
