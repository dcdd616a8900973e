"""Host ms per run in the flow driver's own work: the `flow.plan`,
`flow.world` and `flow.reconcile` spans (core/flowplan.py)."""
from benchmark import flowscopes


def read(ctx):
    return flowscopes.span_ms_per_run(
        ctx, ("flow.plan", "flow.world", "flow.reconcile"))
