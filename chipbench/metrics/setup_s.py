"""setup_s: process start to the window's start, compilation included."""


def read(rec):
    return rec.setup_s
