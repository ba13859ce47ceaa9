"""Images delivered to the host in the window, over its seconds.

The image in progress when the window closes counts by the share of its
own time, launch to image on the host, that fell inside the window: a
whole count would step by one image (about 1% at 100 images), a step that
is no change in the system."""


def read(run):
    t = run.seconds
    done = len(run.done_in(t))
    for r in run.requests:
        if r["launch"] is not None and r["launch"] < t and r["done"] is not None \
                and r["done"] > t:
            done += (t - r["launch"]) / (r["done"] - r["launch"])
    return done / t
