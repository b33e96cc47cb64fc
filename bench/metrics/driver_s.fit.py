"""Seconds per fit of the driver race (its FCM and WFCMPB runs on the
sample), from the fit's diagnostics: ``t_fcm_driver + t_wfcmpb_driver``."""


def read(run):
    fits = run.record.get("fits")
    if not fits or not run.cell.config.get("use_driver"):
        return None
    return sum(f["driver_s"] for f in fits) / len(fits)
