"""Rows per device dispatch of the scoring service in the window:
``serve.records`` over ``serve.batches``."""


def read(run):
    obs = run.record.get("obs")
    if not obs or not obs["batches"]:
        return None
    return obs["records"] / obs["batches"]
