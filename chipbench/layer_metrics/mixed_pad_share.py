"""Model step: the padding a mixed step computes (%): 1 - the real
prompt tokens of its pieces (`chunk_tokens`) over the rows the program
runs for them (`b_pre` piece rows x the `t` bucket), summed over the
mixed dispatches of the flight records before the traced slice. None
where none ran, or for a program without the timeline."""
from chipbench import timeline


def read(ctx):
    return timeline.mixed_pad_share(timeline.of_part(ctx, "before"))
