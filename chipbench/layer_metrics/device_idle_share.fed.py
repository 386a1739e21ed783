"""``device_idle_share.fed``: the same reading as ``device_idle_share``, under a name of its own
because the fed cell reports another end-to-end metric (``fed_img_per_s``)."""

import importlib.util
import os

_spec = importlib.util.spec_from_file_location(
    "chipbench_layer_metrics_device_idle_share",
    os.path.join(os.path.dirname(os.path.abspath(__file__)), "device_idle_share.py"))
_mod = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(_mod)
read = _mod.read
