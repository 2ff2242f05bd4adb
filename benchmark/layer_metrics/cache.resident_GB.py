"""GB of cached batches on the device after the window: the program's
gauge (`utils/metrics.cache_resident_bytes`: the registered bytes of every
device-cached relation's buffers that are on the device when it is read;
what a spill took away and did not bring back is not in it). `q6_cached`:
the seven columns of 60,000,000 rows, 2.2 GB. Nothing on a program
without the gauge."""


def read(run):
    from spark_rapids_tpu.utils import metrics as M

    gauge = getattr(M, "cache_resident_bytes", None)
    if gauge is None:
        return None
    return gauge() / 1e9
