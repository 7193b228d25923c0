"""The DSVRG inner-direction kernel's share of its roofline (device
trace; work from ``bench/cost/odm_svrg_grad.py``, one minibatch of
``batch`` rows per call); moves ``stream_rows_per_s``."""
from harness.layers import roofline_pct


def read(r):
    c = r.counters
    if not c.get("stream.fits"):
        return None

    def work(mod, calls):
        return [(*mod.cost(c["stream.batch"], c["stream.features"]), calls)]

    return roofline_pct(r, "odm_svrg_grad", work)
