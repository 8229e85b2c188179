"""Driver layer (``repro.api.session.ElasticSession``): median over the
traced window's chunks of ``RoundRecord.dispatch_ms``, the host's time from
``RoundInputs`` to the jit call's return, host-to-device copies included."""
import statistics


def read(run):
    return statistics.median(run.dispatch_ms) if run.dispatch_ms else None
