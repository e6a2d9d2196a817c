"""``feed_wait_frac`` (layer: feed): ``TrainMetrics.infeed_time`` over
``TrainMetrics.step_time`` across the window: the share of the consumer
loop's time that the feed's consumer thread spent waiting for records.
Host wait, not device idle time."""


def read(facts):
    if not facts.get("metrics_step_time_s"):
        return None
    return facts["infeed_wait_s"] / facts["metrics_step_time_s"]
