"""``conv_device_ms`` — compiled step: device time per traced step of the
operations whose root primitive is a convolution
(``conv_general_dilated`` ends the ``op_name``), forward or backward, self
time, averaged over the chips.  A fusion is billed to its root, so a
convolution fused under another root is not counted and what is fused
under a convolution is."""
import scope_reduce


def read(run):
    return scope_reduce.per_step_ms(run, "convolution")
