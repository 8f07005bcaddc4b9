"""The whole job's share of the MESH's peak: the least time one chip
could take for the work one job needs by the algorithm (the
configuration's ``job_roofline``, from shapes alone), over the chips
that share it (``data_shards`` of the window's ``gbm.chunk`` spans), over
the wall time of a job in the traced window. ``step_mfu``'s formula
where ``data_shards`` is 1; ``step_mfu`` itself divides by one chip's
peak alone, and so reads a four-chip job four times too high. Nothing
where the spans do not say how the job was split."""

from benchmark.layer_metrics import mesh_planes as mp


def read(r):
    shards = mp.data_shards(r)
    least = r.least_seconds(r.config["job_roofline"], r.shapes)
    if not shards or least is None or "fit_s" not in r.end_to_end:
        return None
    return r.share_pct(least[0] / shards, r.end_to_end["fit_s"],
                       "mesh_step_mfu")
