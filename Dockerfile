# Cordum-TPU control plane image.
#
# One image, six entrypoints: the service is selected with CORDUM_SERVICE
# (statebus | safety-kernel | scheduler | workflow-engine | gateway | worker),
# mirroring the reference's single-binary-per-container layout
# (reference Dockerfile + docker-compose.yml) without six separate builds.
#
# The worker container is the only one that needs a TPU: on GKE it is
# scheduled onto TPU node pools via the manifests in deploy/k8s/ (node
# selectors + google.com/tpu resources); every other service is pure CPU.
FROM python:3.12-slim

# gcc for the native strategy-scan hot loop (built from source at first use;
# binaries are never shipped in the image or the repo)
RUN apt-get update && apt-get install -y --no-install-recommends gcc libc6-dev \
    && rm -rf /var/lib/apt/lists/*

WORKDIR /app
COPY cordum_tpu/ cordum_tpu/
COPY config/ config/
COPY examples/ examples/

# control-plane deps (jax is only required by the worker image variant; the
# control plane runs without it)
RUN pip install --no-cache-dir aiohttp msgpack pyyaml jsonschema cryptography

# worker variant: docker build --build-arg WITH_TPU=1 ... installs jax for
# the in-tree TPU worker (the TPU runtime/libtpu comes from the node image)
ARG WITH_TPU=0
RUN if [ "$WITH_TPU" = "1" ]; then \
      pip install --no-cache-dir "jax[tpu]" -f https://storage.googleapis.com/jax-releases/libtpu_releases.html; \
    fi

ENV PYTHONUNBUFFERED=1 \
    CORDUM_SERVICE=gateway \
    CORDUM_STATEBUS_URL=statebus://statebus:7420

CMD ["sh", "-c", "python -m cordum_tpu.cmd.$(echo $CORDUM_SERVICE | tr - _)"]
