"""The FusedMBConv kernels' share of their roofline, in %: the least
time the chip could take for their work, the larger of its operations
over the peak of the configuration's arithmetic and its HBM bytes over
the chip's HBM bandwidth (``bench/peaks.json``), over their device time
(``fmbconv_ms_per_batch``'s ops), per program execution.  The work is
the configuration's reference's ``fmbconv_work(cfg, batch)`` at each
dispatched batch's bucket.  None where the trace holds no such op or
the reference counts no such work.  Layer: kernels
(``kernels/fmbconv``)."""
from benchlib import spec


def _kernel_seconds(run) -> float:
    ms = spec.load_module(spec.BENCH_DIR / "metrics"
                          / "fmbconv_ms_per_batch.py",
                          "bench_metric_fmbconv_ms_per_batch")
    return ms.seconds(run)


def hbm_bytes_per_s(run) -> float | None:
    """The bandwidth of the chip whose peak the run was given."""
    key = run.cell.config["peak"]
    devices = spec.load_json(spec.BENCH_DIR / "peaks.json")["devices"]
    for d in devices.values():
        if d.get(key) == run.peak:
            return d.get("hbm_bytes_per_s")
    return None


def read(run):
    n = run.forwards()
    if run.device is None or not n or not run.peak:
        return None
    work = getattr(run.cell.reference, "fmbconv_work", None)
    buckets = [attrs["bucket"] for *_x, attrs, _s, _p
               in run.spans_in_window("dispatch")]
    bw = hbm_bytes_per_s(run)
    t = _kernel_seconds(run) / n
    if work is None or not buckets or not bw or not t:
        return None
    per = [work(run.cell.config, b) for b in buckets]
    flops = sum(f for f, _ in per) / len(per)
    nbytes = sum(b for _, b in per) / len(per)
    return 100.0 * max(flops / run.peak, nbytes / bw) / t
