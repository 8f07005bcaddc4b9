"""Host seconds of set-up spent making the data from the seed and
building the frame (``Frame.from_numpy`` through ``block_until_ready``)."""


def read(r):
    return r.setup_seconds["setup_frame"]
