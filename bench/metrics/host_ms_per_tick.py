"""Host path: mean wall time from a tick's start until ``FleetRuntime.run``
returns, before the wait for the device, over the window's ticks (ms)."""


def read(run):
    if not run.ticks:
        return None
    return 1e3 * sum(t.host_s for t in run.ticks) / len(run.ticks)
