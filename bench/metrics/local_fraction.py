"""Share of the prefills routed in the window that the coordinator kept on
the decode worker (the packed local step) rather than sending to the
prefill worker (program counters ``local_count`` / ``total_routed``)."""

COUNTERS = ("coordinator.local_count", "coordinator.total_routed")


def read(run):
    routed = run.delta("coordinator.total_routed")
    return (100.0 * run.delta("coordinator.local_count") / routed
            if routed else None)
