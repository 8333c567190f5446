"""Model step: a mixed dispatch's time on the device with NO profiler,
median (ms): `dev_ms` of the mixed dispatches read in the flight records
before the traced slice: the return of a readback that blocked less the
dispatch's start (its own launch if the device was empty, else the
finish of the dispatch before it; dynamo_tpu/telemetry/flight.py says
where it is absent). The untraced twin of `mixed_step_device_ms`, over
some hundred steps and not a slice's few dozen. None where no mixed
dispatch has both ends, or for a program without the timeline."""
from chipbench import timeline


def read(ctx):
    return timeline.mixed_step_ms_p50(timeline.of_part(ctx, "before"))
