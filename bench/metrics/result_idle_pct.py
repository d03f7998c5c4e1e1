"""Share of the traced window in which a device is idle while the host
waits for the count's result or fetches it (the program's ``tc.wait``
and ``tc.fetch`` spans), averaged over the devices used."""
from bench import spans


def read(run):
    return spans.idle_under_pct(run, (spans.WAIT, spans.FETCH))
