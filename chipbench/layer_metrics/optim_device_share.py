"""``optim_device_share``: the share of the device's busy time that goes to
the optimizer's update: operations under the program's scope ``optim``
(``optimizer.update`` in the train step), by stable name from the reduced
trace, over the busy seconds; mean over the cell's devices. An update that
XLA fused into a weight-gradient convolution keeps the convolution's name
and is not counted here. A program without the scope gives nothing to read."""

import importlib.util
import os

_spec = importlib.util.spec_from_file_location(
    "chipbench_layer_metrics_data_device_share",
    os.path.join(os.path.dirname(os.path.abspath(__file__)), "data_device_share.py"))
_mod = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(_mod)


def read(ctx):
    return _mod.share(ctx, ("optim/",))
