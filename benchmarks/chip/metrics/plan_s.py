"""Seconds in `get_engine` and `device_matvec` during set-up (schedule,
device plan, matvec executable), less the compile seconds inside them."""


def read(run):
    return run.plan_s
