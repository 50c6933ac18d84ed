"""Share of its roofline that the ``masked_select`` kernel reaches, %.

Bytes-bound: the least bytes of each call (``peaks.masked_select_bytes``
of its rows, read from the event's HLO text, and the cluster's real
device count) over the HBM peak of the device kind, against the summed
device time of the kernel's events in the trace."""

from benchmarks.chip.peaks import masked_select_bytes, peak


def read(ctx):
    calls = (ctx.reduced or {}).get("kernels", {}).get("masked_select")
    if not calls or None in calls:
        return None
    need = sum(n * masked_select_bytes(rows, ctx.n_osds)
               for rows, (n, _) in calls.items())
    seconds = sum(sec for _, sec in calls.values())
    return 100.0 * need / peak(ctx.device_kind, "hbm_bytes_per_s") / seconds
