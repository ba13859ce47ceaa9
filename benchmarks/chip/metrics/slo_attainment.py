"""Requests delivered by their deadline, over requests sent."""


def read(run):
    on_time = sum(1 for r in run.requests
                  if r["done"] is not None and r["done"] <= r["deadline"])
    return on_time / len(run.requests) if run.requests else None
