"""Device time of the operations compiled under the scope ``rdp.optimizer``
(the Adam update and ``apply_updates`` of ``core_train_step``), per
optimiser step. The scope of an operation is read from the HLO that the
profiler files beside the trace (``lib/xplane_hlo.py``). A fusion belongs to
the scope of its root instruction, so an update that XLA fuses into a
gradient's last operation is booked with that gradient, not here."""

from perfbench.lib import spans

SCOPE = "rdp.optimizer"


def read(ctx):
    steps = ctx.counters.get("optimizer_steps")
    seconds = spans.of(ctx).device_seconds(SCOPE)
    if not steps or seconds <= 0:
        return None
    return 1e3 * seconds / steps
