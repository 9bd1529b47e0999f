"""setup_s: process start to the first timed unit (host clock): weights
made on the device, planning, compilation or cache load, warm-up."""


def read(r):
    return r.setup_s
