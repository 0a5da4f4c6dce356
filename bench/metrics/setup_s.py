"""Seconds from process start to the first due request: imports, data and
weights, plan, engine, and the warm-up that compiles or loads every stage
program."""


def read(run):
    return run.setup_s
