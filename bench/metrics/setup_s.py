"""Seconds from process start to the window's start: loading, drawing the
weights, compiling or fetching every program, warming up."""


def read(rec):
    return rec["setup_s"]
