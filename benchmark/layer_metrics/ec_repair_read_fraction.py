"""Helper bytes a repair was handed over the bytes of object it
acknowledged: the program's ``l_tpu_ec_repair_helper_bytes`` (counted
in ``ec/stripe.repair``) over the window's amount.  d / (k (d - k + 1))
when the code's minimum-bandwidth repair is engaged (11/32 = 0.34375
at k=8 m=4 d=11), 1.0 on whole chunks of k helpers.  A program without
the counter gives nothing."""


def read(run):
    counters = run["counters"]
    amount = run["client"]["amount"]
    handed = counters.get("l_tpu_ec_repair_helper_bytes", 0)
    if "calls" not in counters or not handed or not amount:
        return None
    return handed / amount
