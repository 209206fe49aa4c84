"""Mean device-idle time between one execution of the serve step and
the next: the host work the chip waits for (admission, block tables,
the logits fetch, sampling)."""
import xplane


def reduce(tr, facts):
    dev = tr.devices[0]
    ex = xplane.executions(tr, dev)
    if len(ex) < 2:
        return None
    idle = [sum(e - s for s, e in xplane.gaps(dev.ops, a[2], b[1]))
            for a, b in zip(ex, ex[1:])]
    return sum(idle) / len(idle) / 1e6
