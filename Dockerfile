# torchbeast_tpu — CPU image (runs the full test suite on 8 virtual
# devices; on a TPU VM install the matching jax[tpu] wheel instead).
# The reference's image (Dockerfile:1-106) builds conda + gRPC + torch;
# this one is pip + g++ only.
FROM python:3.12-slim

RUN apt-get update && apt-get install -y --no-install-recommends \
        g++ make git && \
    rm -rf /var/lib/apt/lists/*

WORKDIR /opt/torchbeast_tpu

# Deps first so source edits don't invalidate the install layer.
RUN pip install --no-cache-dir setuptools jax flax optax numpy pytest

COPY pyproject.toml setup.py ./
COPY scripts/ scripts/
COPY csrc/ csrc/
COPY torchbeast_tpu/ torchbeast_tpu/
COPY tests/ tests/
COPY benchmarks/ benchmarks/
COPY chip_smoke.py __graft_entry__.py ./

RUN bash scripts/build_native.sh

# Atari support (optional): pip install gymnasium ale-py opencv-python-headless

RUN python -m pytest tests/ -q

ENTRYPOINT ["python", "-m", "torchbeast_tpu.polybeast"]
CMD ["--env", "Mock", "--total_steps", "100000"]
