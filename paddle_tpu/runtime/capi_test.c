/* Pure-C embedding test for the ptrt inference ABI.
 *
 * Compiled with plain gcc; links NOTHING but libdl — the ptrt .so is
 * dlopen'd, exactly how a third-party C application would embed the
 * predictor (reference counterpart: paddle/legacy/capi examples, the C
 * consumer of paddle_inference_api.h).
 *
 * Usage:
 *   capi_test <ptrt_capi.so> <model_dir> \
 *             <feed_name> <dtype> <dims d0,d1,..> <raw file> \
 *             <expected_out raw float32 file> <rtol> [bench_iters]
 *
 * Exit 0 iff the model loads, runs, and fetch 0 matches the expected
 * buffer elementwise within rtol. With bench_iters > 0, additionally
 * times cold start (dlopen + predictor_load), the first run, and
 * bench_iters steady-state runs, printing one BENCH line.
 */
#include <dlfcn.h>
#include <math.h>
#include <stdint.h>
#include <stdio.h>
#include <stdlib.h>
#include <string.h>
#include <time.h>

#include "ptrt_capi.h"

static double now_ms(void) {
  struct timespec ts;
  clock_gettime(CLOCK_MONOTONIC, &ts);
  return ts.tv_sec * 1e3 + ts.tv_nsec / 1e6;
}

static void *load_file(const char *path, long *size) {
  FILE *f = fopen(path, "rb");
  if (!f) return NULL;
  fseek(f, 0, SEEK_END);
  *size = ftell(f);
  fseek(f, 0, SEEK_SET);
  void *buf = malloc(*size ? *size : 1);
  if (fread(buf, 1, *size, f) != (size_t)*size) {
    fclose(f);
    free(buf);
    return NULL;
  }
  fclose(f);
  return buf;
}

int main(int argc, char **argv) {
  if (argc != 9 && argc != 10) {
    fprintf(stderr, "usage: %s so model_dir feed dtype dims file "
                    "expected rtol [bench_iters]\n", argv[0]);
    return 2;
  }
  const char *so = argv[1], *model_dir = argv[2];
  const double rtol = atof(argv[8]);
  const long bench_iters = argc == 10 ? atol(argv[9]) : 0;

  double t_start = now_ms();
  void *lib = dlopen(so, RTLD_NOW | RTLD_GLOBAL);
  if (!lib) {
    fprintf(stderr, "dlopen: %s\n", dlerror());
    return 2;
  }
  ptrt_predictor *(*load)(const char *) =
      (ptrt_predictor * (*)(const char *)) dlsym(lib, "ptrt_predictor_load");
  int (*run)(ptrt_predictor *, const ptrt_tensor *, int32_t,
             ptrt_tensor **, int32_t *) =
      (int (*)(ptrt_predictor *, const ptrt_tensor *, int32_t,
               ptrt_tensor **, int32_t *))dlsym(lib, "ptrt_predictor_run");
  const char *(*last_error)(void) =
      (const char *(*)(void))dlsym(lib, "ptrt_last_error");
  void (*tensors_free)(ptrt_tensor *, int32_t) =
      (void (*)(ptrt_tensor *, int32_t))dlsym(lib, "ptrt_tensors_free");
  void (*pred_free)(ptrt_predictor *) =
      (void (*)(ptrt_predictor *))dlsym(lib, "ptrt_predictor_free");
  int32_t (*num_feeds)(ptrt_predictor *) =
      (int32_t (*)(ptrt_predictor *))dlsym(lib, "ptrt_predictor_num_feeds");
  if (!load || !run || !last_error || !tensors_free || !pred_free ||
      !num_feeds) {
    fprintf(stderr, "dlsym failed: %s\n", dlerror());
    return 2;
  }

  ptrt_predictor *p = load(model_dir);
  if (!p) {
    fprintf(stderr, "load failed: %s\n", last_error());
    return 1;
  }
  double load_ms = now_ms() - t_start;
  if (num_feeds(p) < 1) {
    fprintf(stderr, "model has no feeds\n");
    return 1;
  }

  ptrt_tensor in;
  memset(&in, 0, sizeof(in));
  snprintf(in.name, sizeof(in.name), "%s", argv[3]);
  snprintf(in.dtype, sizeof(in.dtype), "%s", argv[4]);
  in.ndim = 0;
  char *dims = strdup(argv[5]);
  for (char *tok = strtok(dims, ","); tok; tok = strtok(NULL, ",")) {
    if (in.ndim >= PTRT_MAX_DIMS) {
      fprintf(stderr, "too many dims (max %d)\n", PTRT_MAX_DIMS);
      free(dims);
      return 2;
    }
    in.dims[in.ndim++] = atoll(tok);
  }
  free(dims);
  long nbytes = 0;
  in.data = load_file(argv[6], &nbytes);
  if (!in.data) {
    fprintf(stderr, "cannot read feed file %s\n", argv[6]);
    return 2;
  }
  in.nbytes = nbytes;

  ptrt_tensor *outs = NULL;
  int32_t n_out = 0;
  double t_run0 = now_ms();
  if (run(p, &in, 1, &outs, &n_out) != 0) {
    fprintf(stderr, "run failed: %s\n", last_error());
    return 1;
  }
  double first_run_ms = now_ms() - t_run0;
  if (n_out < 1) {
    fprintf(stderr, "no fetch outputs\n");
    return 1;
  }

  long esize = 0;
  float *expected = (float *)load_file(argv[7], &esize);
  if (!expected) {
    fprintf(stderr, "cannot read expected file %s\n", argv[7]);
    return 2;
  }
  if (strcmp(outs[0].dtype, "float32") != 0) {
    fprintf(stderr, "fetch 0 dtype %s, want float32\n", outs[0].dtype);
    return 1;
  }
  if (outs[0].nbytes != esize) {
    fprintf(stderr, "fetch 0 has %lld bytes, expected %ld\n",
            (long long)outs[0].nbytes, esize);
    return 1;
  }
  const float *got = (const float *)outs[0].data;
  long n = esize / (long)sizeof(float);
  double worst = 0.0;
  for (long i = 0; i < n; ++i) {
    double denom = fabs((double)expected[i]) + 1e-8;
    double rel = fabs((double)got[i] - (double)expected[i]) / denom;
    if (rel > worst) worst = rel;
  }
  printf("compared %ld values, worst rel err %.3g (rtol %.3g)\n", n, worst,
         rtol);
  tensors_free(outs, n_out);

  if (bench_iters > 0) {
    double total = 0.0, best = 1e30;
    for (long it = 0; it < bench_iters; ++it) {
      ptrt_tensor *bo = NULL;
      int32_t bn = 0;
      double t0 = now_ms();
      if (run(p, &in, 1, &bo, &bn) != 0) {
        fprintf(stderr, "bench run failed: %s\n", last_error());
        return 1;
      }
      double dt = now_ms() - t0;
      total += dt;
      if (dt < best) best = dt;
      tensors_free(bo, bn);
    }
    printf("BENCH load_ms=%.1f first_run_ms=%.1f run_ms_min=%.3f "
           "run_ms_mean=%.3f iters=%ld\n",
           load_ms, first_run_ms, best, total / bench_iters, bench_iters);
  }

  pred_free(p);
  free(in.data);
  free(expected);
  if (worst > rtol) {
    fprintf(stderr, "MISMATCH\n");
    return 1;
  }
  printf("OK\n");
  return 0;
}
