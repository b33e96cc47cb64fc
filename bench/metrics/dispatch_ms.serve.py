"""Mean milliseconds of the service worker's ``serve.assign`` span
(upload, score, blocking copy back) in the window."""


def read(run):
    obs = run.record.get("obs")
    if not obs or not obs["assign_n"]:
        return None
    return 1e3 * obs["assign_s"] / obs["assign_n"]
