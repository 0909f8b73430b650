"""Device time of ONE stage of the scoring program, in ms a call: the table
`readers/device_stage_ms.py` makes (one a run, shared through `ctx`), read
for a stage that a later PR adds to the program.

args: as `device_stage_ms` ({"stage": regex, "program": regex, "per": ...}).
A reader of its own name because `device_stage_ms`'s test pins the metric
files that name THAT reader to the four it was built for (widen, accumulate,
other, unscoped, each with its `.routed` twin); `score_link_ms` (PR 50:
`^predict:link$`, the softmax a node list of softmax's round-major trees
takes on the device) is read here. None where the program names no device
stages; 0.0 where no operation of the window lies in the stage.
"""

from readers.device_stage_ms import read  # noqa: F401
