"""Whole step: implementation-independent work per second over the chip's
int8 peak, in %.  The work is 2 operations per multiply-accumulate of the
plaintext forward, once per party, for every image served in the window
(``work.step_ops_per_image``); the int8 peak is the protocol's matmul
precision (int8 digit planes)."""
from cellbench import work


def read(run):
    if not run["images_per_s"]:
        return None
    chips = run["cell"]["chips"]
    return (100.0 * work.step_ops_per_image(run["config"])
            * run["images_per_s"] / (chips * run["peaks"]["int8_ops_per_s"]))
