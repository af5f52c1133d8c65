"""Seconds of the cluster's Eq. 1 probe in set-up: the program's
``cluster.probe`` spans over the whole process (``repro.tracing``).
Nothing to read where the program keeps no totals or did not probe."""


def read(m):
    try:
        from repro import tracing
    except ImportError:
        return None
    probe = [total_s for (name, _), (total_s, _, _) in tracing.span_totals().items()
             if name == "cluster.probe"]
    return sum(probe) if probe else None
