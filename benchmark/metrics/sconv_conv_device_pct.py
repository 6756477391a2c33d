"""Share of a step's device operations that carry the program's scope
``sconv/conv`` (through ``benchmark/step_scopes.py``): what XLA leaves
UNFUSED of the gated short convolution's middle (``z = B * X``, the three
taps, ``C * c``, forward and backward).  It is NOT the middle's cost: at
the cell's size XLA fuses ``B * X`` and the taps into the product behind
them (``convolution_bitcast_fusion f32[1,8192,6144]``, 44 ms a step,
booked under ``sconv/project``) and what is left here is four multiplies
of ``[1, 8192, 2048]``, 20 ms.  A kernel that takes the taps out of that
fusion would RAISE this share while the step got faster: read it beside
``short_conv_mixer_roofline``, which takes both scopes against the mixer
as written and cannot be moved by where a fusion is booked."""
from benchmark import step_scopes


def read(run):
    return step_scopes.share(run, "sconv/conv")
