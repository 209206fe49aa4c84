"""Serve-step executions of the traced window paired in order with the
kinds of the ticks the harness dispatched while tracing."""
import xplane


def mean_ms(tr, facts, kind):
    ex = xplane.executions(tr, tr.devices[0])
    kinds = facts.get("tick_kinds", [])
    if not ex or len(ex) != len(kinds):
        return None
    ms = [(e - s) / 1e6 for (_, s, e), k in zip(ex, kinds) if k == kind]
    return sum(ms) / len(ms) if ms else None
