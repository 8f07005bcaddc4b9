"""The whole job's share of the chip's peak: the least time the chip
could take for the work one job needs by the algorithm (the
configuration's ``job_roofline``, from shapes alone, the same whatever
implements it) over the wall time of a job in the traced window."""


def read(r):
    least = r.least_seconds(r.config["job_roofline"], r.shapes)
    if least is None or "fit_s" not in r.end_to_end:
        return None
    return r.share_pct(least[0], r.end_to_end["fit_s"], "step_mfu")
