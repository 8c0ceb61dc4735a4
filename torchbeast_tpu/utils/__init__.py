import logging

from torchbeast_tpu.utils.checkpoint import (  # noqa: F401
    load_checkpoint,
    save_checkpoint,
)
from torchbeast_tpu.utils.file_writer import FileWriter  # noqa: F401
from torchbeast_tpu.utils.prof import Timings  # noqa: F401
from torchbeast_tpu.utils.preempt import install_preemption_handler  # noqa: F401


def configure_logging():
    """The drivers' log format. Called from a driver's main(), NOT at
    import: importing a driver (as every test does, and as the drivers
    do of each other) must not mutate global logging state."""
    logging.basicConfig(
        format=(
            "[%(levelname)s:%(process)d %(module)s:%(lineno)d "
            "%(asctime)s] %(message)s"
        ),
        level=logging.INFO,
    )
