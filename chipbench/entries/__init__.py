"""Timed entries: one module per kind of program entry the window drives
(train step, serving engine), named by a traffic file's ``entry`` key.
Each exposes ``run(cell, *, seed, seconds, trace, t_start, peaks)
-> result.Result``."""
