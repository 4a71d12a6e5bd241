from repro.checkpoint.io import (FORMAT_VERSION,  # noqa: F401
                                 checkpoint_meta, checkpoint_step,
                                 load_checkpoint, load_state,
                                 save_checkpoint, save_state)
