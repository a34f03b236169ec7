"""Device time of the flash attention kernels per optimizer step: own time
of the ``XLA Ops`` events named ``slt_flash_fwd``, ``slt_flash_bwd_dq`` and
``slt_flash_bwd_dkv`` inside the ``sl_train_step`` programs (under the
scopes ``attn_window`` and ``attn_full``), mean over the chips."""

import mixer_trace


def read(run):
    return mixer_trace.scope_ms(run, "flash")
