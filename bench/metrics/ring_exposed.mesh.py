"""Share of the traced window in which a collective runs on a device and no
other op does, averaged over the devices."""


def read(r):
    if r.trace is None:
        return None
    share = r.trace.collective_exposed_share()
    return None if share is None else 100.0 * share
