# Single-environment image. The reference needed TWO conda environments in
# one container because PWC-Net's CuPy CUDA kernel pinned torch 1.2 + CUDA 10
# while everything else ran torch 1.7 + CUDA 11 (reference Dockerfile,
# conda_env_pwc.yml, conda_env_torch_zoo.yml). The PWC cost volume here is a
# Pallas/XLA kernel, so one environment serves every model family.
FROM python:3.12-slim

RUN apt-get update && apt-get install -y --no-install-recommends \
        ffmpeg libgl1 libglib2.0-0 \
    && rm -rf /var/lib/apt/lists/*

WORKDIR /opt/video_features_tpu
COPY pyproject.toml README.md ./
COPY video_features_tpu ./video_features_tpu
COPY main.py bench.py ./
COPY scripts ./scripts

# CPU jax 0.9.0 by default (pyproject.toml pins it); on TPU VMs add libtpu:
#   pip install -e ".[tpu]"      # jax[tpu]==0.9.0, libtpu==0.0.34
RUN pip install --no-cache-dir -e ".[convert]"

# converted weights cache (mount a volume here; see scripts/convert_weights.py)
ENV VFT_WEIGHTS_DIR=/weights
VOLUME /weights

ENTRYPOINT ["python", "main.py"]
