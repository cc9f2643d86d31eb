// PJRT runtime bridge — the framework's native tensor-runtime layer.
//
// Role parity with the reference's native stack (reference:
// deeplearning4j consumes ND4J whose C++ backend `libnd4j` plus the
// JavaCPP JNI bridges execute every tensor op; SURVEY.md §2.9 row 1
// maps that role to a "C++ PJRT bridge ... lowered to XLA computations
// executed via the PJRT C API"). Where libnd4j hand-implements kernels,
// on TPU the kernels come from XLA; what remains native is exactly this
// layer: plugin loading, client/device lifecycle, program compilation,
// HBM buffer management and H2D/D2H transfer, executable dispatch.
//
// The exported C ABI is consumed from Python via ctypes
// (deeplearning4j_tpu/pjrt.py) — the same "thin host API over a native
// runtime" shape as ND4J-over-libnd4j, without JNI.
//
// Every PJRT call follows the C-API conventions: args structs with
// struct_size set to the *_STRUCT_SIZE constant, PJRT_Error* returns
// that must be freed via PJRT_Error_Destroy, and async results
// surfaced as PJRT_Event* that we await + destroy before returning.

#include <dlfcn.h>

#include <condition_variable>
#include <cstdint>
#include <cstring>
#include <deque>
#include <mutex>
#include <string>
#include <thread>
#include <unordered_map>
#include <vector>

#include "pjrt_c_api.h"

namespace {

// Copy a PJRT error's message into the caller's buffer and free it.
void consume_error(const PJRT_Api* api, PJRT_Error* err, char* out,
                   int outlen) {
  if (err == nullptr) return;
  PJRT_Error_Message_Args margs;
  std::memset(&margs, 0, sizeof(margs));
  margs.struct_size = PJRT_Error_Message_Args_STRUCT_SIZE;
  margs.error = err;
  api->PJRT_Error_Message(&margs);
  if (out != nullptr && outlen > 0) {
    size_t n = margs.message_size < static_cast<size_t>(outlen - 1)
                   ? margs.message_size
                   : static_cast<size_t>(outlen - 1);
    std::memcpy(out, margs.message, n);
    out[n] = '\0';
  }
  PJRT_Error_Destroy_Args dargs;
  std::memset(&dargs, 0, sizeof(dargs));
  dargs.struct_size = PJRT_Error_Destroy_Args_STRUCT_SIZE;
  dargs.error = err;
  api->PJRT_Error_Destroy(&dargs);
}

void set_err(char* out, int outlen, const char* msg) {
  if (out != nullptr && outlen > 0) {
    std::snprintf(out, outlen, "%s", msg);
  }
}

// Await an event, free it, and surface any error. Returns 0 on success.
int await_and_destroy(const PJRT_Api* api, PJRT_Event* event, char* err,
                      int errlen) {
  if (event == nullptr) return 0;
  PJRT_Event_Await_Args aargs;
  std::memset(&aargs, 0, sizeof(aargs));
  aargs.struct_size = PJRT_Event_Await_Args_STRUCT_SIZE;
  aargs.event = event;
  PJRT_Error* e = api->PJRT_Event_Await(&aargs);
  PJRT_Event_Destroy_Args dargs;
  std::memset(&dargs, 0, sizeof(dargs));
  dargs.struct_size = PJRT_Event_Destroy_Args_STRUCT_SIZE;
  dargs.event = event;
  api->PJRT_Event_Destroy(&dargs);
  if (e != nullptr) {
    consume_error(api, e, err, errlen);
    return -1;
  }
  return 0;
}

}  // namespace

extern "C" {

// ---- plugin / api ----------------------------------------------------

// dlopen a PJRT plugin (.so exporting `GetPjrtApi`, e.g. libtpu.so) and
// return its PJRT_Api*, or null (error text in `err`).
const void* dl4j_pjrt_load(const char* so_path, char* err, int errlen) {
  void* handle = dlopen(so_path, RTLD_NOW | RTLD_LOCAL);
  if (handle == nullptr) {
    set_err(err, errlen, dlerror());
    return nullptr;
  }
  using GetPjrtApiFn = const PJRT_Api* (*)();
  auto get_api =
      reinterpret_cast<GetPjrtApiFn>(dlsym(handle, "GetPjrtApi"));
  if (get_api == nullptr) {
    set_err(err, errlen, "plugin does not export GetPjrtApi");
    dlclose(handle);
    return nullptr;
  }
  const PJRT_Api* api = get_api();
  if (api == nullptr) {
    set_err(err, errlen, "GetPjrtApi returned null");
    return nullptr;
  }
  if (api->PJRT_Plugin_Initialize != nullptr) {
    PJRT_Plugin_Initialize_Args args;
    std::memset(&args, 0, sizeof(args));
    args.struct_size = PJRT_Plugin_Initialize_Args_STRUCT_SIZE;
    PJRT_Error* e = api->PJRT_Plugin_Initialize(&args);
    if (e != nullptr) {
      consume_error(api, e, err, errlen);
      return nullptr;
    }
  }
  return api;
}

void dl4j_pjrt_api_version(const void* api_p, int* major, int* minor) {
  const PJRT_Api* api = static_cast<const PJRT_Api*>(api_p);
  *major = api->pjrt_api_version.major_version;
  *minor = api->pjrt_api_version.minor_version;
}

// ---- client ----------------------------------------------------------

void* dl4j_pjrt_client_create(const void* api_p, char* err, int errlen) {
  const PJRT_Api* api = static_cast<const PJRT_Api*>(api_p);
  PJRT_Client_Create_Args args;
  std::memset(&args, 0, sizeof(args));
  args.struct_size = PJRT_Client_Create_Args_STRUCT_SIZE;
  PJRT_Error* e = api->PJRT_Client_Create(&args);
  if (e != nullptr) {
    consume_error(api, e, err, errlen);
    return nullptr;
  }
  return args.client;
}

// Client creation with PJRT_NamedValue create_options. Real plugins
// (libtpu) may require session/topology options at client creation; the parallel arrays encode n options of kind 0
// (string: str_vals[i]), kind 1 (int64: int_vals[i]) or kind 2
// (bool: int_vals[i] != 0) — keep this list in sync with the switch
// below and pjrt.py's marshalling. Role parity:
// ND4J backends pass CudaEnvironment-style config into libnd4j at
// backend init (SURVEY §2.9 row 1).
void* dl4j_pjrt_client_create_opts(const void* api_p, const char** keys,
                                   const char** str_vals,
                                   const long long* int_vals,
                                   const int* kinds, int n, char* err,
                                   int errlen) {
  const PJRT_Api* api = static_cast<const PJRT_Api*>(api_p);
  std::vector<PJRT_NamedValue> opts(static_cast<size_t>(n));
  for (int i = 0; i < n; ++i) {
    PJRT_NamedValue& v = opts[static_cast<size_t>(i)];
    std::memset(&v, 0, sizeof(v));
    v.struct_size = PJRT_NamedValue_STRUCT_SIZE;
    v.name = keys[i];
    v.name_size = std::strlen(keys[i]);
    if (kinds[i] == 0) {
      v.type = PJRT_NamedValue_kString;
      v.string_value = str_vals[i];
      v.value_size = std::strlen(str_vals[i]);
    } else if (kinds[i] == 2) {
      v.type = PJRT_NamedValue_kBool;
      v.bool_value = int_vals[i] != 0;
      v.value_size = 1;
    } else {
      v.type = PJRT_NamedValue_kInt64;
      v.int64_value = static_cast<int64_t>(int_vals[i]);
      v.value_size = 1;
    }
  }
  PJRT_Client_Create_Args args;
  std::memset(&args, 0, sizeof(args));
  args.struct_size = PJRT_Client_Create_Args_STRUCT_SIZE;
  args.create_options = opts.empty() ? nullptr : opts.data();
  args.num_options = opts.size();
  PJRT_Error* e = api->PJRT_Client_Create(&args);
  if (e != nullptr) {
    consume_error(api, e, err, errlen);
    return nullptr;
  }
  return args.client;
}

int dl4j_pjrt_client_destroy(const void* api_p, void* client) {
  const PJRT_Api* api = static_cast<const PJRT_Api*>(api_p);
  PJRT_Client_Destroy_Args args;
  std::memset(&args, 0, sizeof(args));
  args.struct_size = PJRT_Client_Destroy_Args_STRUCT_SIZE;
  args.client = static_cast<PJRT_Client*>(client);
  PJRT_Error* e = api->PJRT_Client_Destroy(&args);
  if (e != nullptr) {
    consume_error(api, e, nullptr, 0);
    return -1;
  }
  return 0;
}

int dl4j_pjrt_platform_name(const void* api_p, void* client, char* out,
                            int outlen) {
  const PJRT_Api* api = static_cast<const PJRT_Api*>(api_p);
  PJRT_Client_PlatformName_Args args;
  std::memset(&args, 0, sizeof(args));
  args.struct_size = PJRT_Client_PlatformName_Args_STRUCT_SIZE;
  args.client = static_cast<PJRT_Client*>(client);
  PJRT_Error* e = api->PJRT_Client_PlatformName(&args);
  if (e != nullptr) {
    consume_error(api, e, nullptr, 0);
    return -1;
  }
  size_t n = args.platform_name_size < static_cast<size_t>(outlen - 1)
                 ? args.platform_name_size
                 : static_cast<size_t>(outlen - 1);
  std::memcpy(out, args.platform_name, n);
  out[n] = '\0';
  return static_cast<int>(n);
}

// Number of devices addressable by this process (HBM-attached chips).
int dl4j_pjrt_device_count(const void* api_p, void* client) {
  const PJRT_Api* api = static_cast<const PJRT_Api*>(api_p);
  PJRT_Client_AddressableDevices_Args args;
  std::memset(&args, 0, sizeof(args));
  args.struct_size = PJRT_Client_AddressableDevices_Args_STRUCT_SIZE;
  args.client = static_cast<PJRT_Client*>(client);
  PJRT_Error* e = api->PJRT_Client_AddressableDevices(&args);
  if (e != nullptr) {
    consume_error(api, e, nullptr, 0);
    return -1;
  }
  return static_cast<int>(args.num_addressable_devices);
}

// ---- compile ---------------------------------------------------------

// Compile an MLIR (StableHLO) module. `compile_options` is a serialized
// xla CompileOptionsProto (may be empty for plugin defaults).
void* dl4j_pjrt_compile_mlir(const void* api_p, void* client,
                             const char* code, size_t code_size,
                             const char* compile_options,
                             size_t compile_options_size, char* err,
                             int errlen) {
  const PJRT_Api* api = static_cast<const PJRT_Api*>(api_p);
  PJRT_Program program;
  std::memset(&program, 0, sizeof(program));
  program.struct_size = PJRT_Program_STRUCT_SIZE;
  program.code = const_cast<char*>(code);
  program.code_size = code_size;
  static const char kFormat[] = "mlir";
  program.format = kFormat;
  program.format_size = sizeof(kFormat) - 1;

  PJRT_Client_Compile_Args args;
  std::memset(&args, 0, sizeof(args));
  args.struct_size = PJRT_Client_Compile_Args_STRUCT_SIZE;
  args.client = static_cast<PJRT_Client*>(client);
  args.program = &program;
  args.compile_options = compile_options;
  args.compile_options_size = compile_options_size;
  PJRT_Error* e = api->PJRT_Client_Compile(&args);
  if (e != nullptr) {
    consume_error(api, e, err, errlen);
    return nullptr;
  }
  return args.executable;
}

int dl4j_pjrt_executable_num_outputs(const void* api_p, void* lexec) {
  const PJRT_Api* api = static_cast<const PJRT_Api*>(api_p);
  PJRT_LoadedExecutable_GetExecutable_Args gargs;
  std::memset(&gargs, 0, sizeof(gargs));
  gargs.struct_size = PJRT_LoadedExecutable_GetExecutable_Args_STRUCT_SIZE;
  gargs.loaded_executable = static_cast<PJRT_LoadedExecutable*>(lexec);
  PJRT_Error* e = api->PJRT_LoadedExecutable_GetExecutable(&gargs);
  if (e != nullptr) {
    consume_error(api, e, nullptr, 0);
    return -1;
  }
  PJRT_Executable_NumOutputs_Args nargs;
  std::memset(&nargs, 0, sizeof(nargs));
  nargs.struct_size = PJRT_Executable_NumOutputs_Args_STRUCT_SIZE;
  nargs.executable = gargs.executable;
  e = api->PJRT_Executable_NumOutputs(&nargs);
  if (e != nullptr) {
    consume_error(api, e, nullptr, 0);
    return -1;
  }
  return static_cast<int>(nargs.num_outputs);
}

int dl4j_pjrt_executable_destroy(const void* api_p, void* lexec) {
  const PJRT_Api* api = static_cast<const PJRT_Api*>(api_p);
  PJRT_LoadedExecutable_Destroy_Args args;
  std::memset(&args, 0, sizeof(args));
  args.struct_size = PJRT_LoadedExecutable_Destroy_Args_STRUCT_SIZE;
  args.executable = static_cast<PJRT_LoadedExecutable*>(lexec);
  PJRT_Error* e = api->PJRT_LoadedExecutable_Destroy(&args);
  if (e != nullptr) {
    consume_error(api, e, nullptr, 0);
    return -1;
  }
  return 0;
}

// ---- buffers ---------------------------------------------------------

// Synchronous H2D: copy a dense row-major host array to device
// `device_ordinal`'s default memory. Returns a PJRT_Buffer*.
// element byte size for the PJRT_Buffer_Type enum values the host API
// uses (pjrt.py _DTYPE_TO_PJRT)
static int64_t dl4j_dtype_size(int dtype) {
  switch (dtype) {
    case 1: case 2: case 6: return 1;            // PRED, S8, U8
    case 3: case 7: case 10: return 2;           // S16, U16, F16
    case 4: case 8: case 11: return 4;           // S32, U32, F32
    case 5: case 9: case 12: return 8;           // S64, U64, F64
    default: return 4;
  }
}

void* dl4j_pjrt_h2d(const void* api_p, void* client, const void* data,
                    int dtype, const int64_t* dims, int ndims,
                    int device_ordinal, char* err, int errlen) {
  const PJRT_Api* api = static_cast<const PJRT_Api*>(api_p);
  PJRT_Client_AddressableDevices_Args dev_args;
  std::memset(&dev_args, 0, sizeof(dev_args));
  dev_args.struct_size = PJRT_Client_AddressableDevices_Args_STRUCT_SIZE;
  dev_args.client = static_cast<PJRT_Client*>(client);
  PJRT_Error* e = api->PJRT_Client_AddressableDevices(&dev_args);
  if (e != nullptr) {
    consume_error(api, e, err, errlen);
    return nullptr;
  }
  if (device_ordinal < 0 ||
      static_cast<size_t>(device_ordinal) >= dev_args.num_addressable_devices) {
    set_err(err, errlen, "device ordinal out of range");
    return nullptr;
  }

  PJRT_Client_BufferFromHostBuffer_Args args;
  std::memset(&args, 0, sizeof(args));
  args.struct_size = PJRT_Client_BufferFromHostBuffer_Args_STRUCT_SIZE;
  args.client = static_cast<PJRT_Client*>(client);
  args.data = data;
  args.type = static_cast<PJRT_Buffer_Type>(dtype);
  args.dims = dims;
  args.num_dims = static_cast<size_t>(ndims);
  // EXPLICIT C-order (row-major) byte strides. Leaving byte_strides
  // empty means "the plugin's default dense layout", and the real TPU
  // plugin's default for rank>=3 buffers is NOT row-major (observed: a
  // clean axis permutation on the (2,3,4) roundtrip) — the host side
  // of this bridge always speaks C-contiguous numpy.
  std::vector<int64_t> strides(static_cast<size_t>(ndims));
  int64_t esize = dl4j_dtype_size(dtype);
  int64_t acc = esize;
  for (int i = ndims - 1; i >= 0; --i) {
    strides[static_cast<size_t>(i)] = acc;
    acc *= dims[i];
  }
  args.byte_strides = strides.empty() ? nullptr : strides.data();
  args.num_byte_strides = strides.size();
  args.host_buffer_semantics =
      PJRT_HostBufferSemantics_kImmutableUntilTransferCompletes;
  args.device = dev_args.addressable_devices[device_ordinal];
  e = api->PJRT_Client_BufferFromHostBuffer(&args);
  if (e != nullptr) {
    consume_error(api, e, err, errlen);
    return nullptr;
  }
  // block until the runtime is done reading the host memory
  if (await_and_destroy(api, args.done_with_host_buffer, err, errlen) != 0) {
    return nullptr;
  }
  return args.buffer;
}

long long dl4j_pjrt_buffer_size(const void* api_p, void* buf) {
  const PJRT_Api* api = static_cast<const PJRT_Api*>(api_p);
  PJRT_Buffer_OnDeviceSizeInBytes_Args args;
  std::memset(&args, 0, sizeof(args));
  args.struct_size = PJRT_Buffer_OnDeviceSizeInBytes_Args_STRUCT_SIZE;
  args.buffer = static_cast<PJRT_Buffer*>(buf);
  PJRT_Error* e = api->PJRT_Buffer_OnDeviceSizeInBytes(&args);
  if (e != nullptr) {
    consume_error(api, e, nullptr, 0);
    return -1;
  }
  return static_cast<long long>(args.on_device_size_in_bytes);
}

// Synchronous D2H. If dst is null, returns the required byte size.
long long dl4j_pjrt_d2h(const void* api_p, void* buf, void* dst,
                        size_t dst_size, char* err, int errlen) {
  const PJRT_Api* api = static_cast<const PJRT_Api*>(api_p);
  // EXPLICIT C-order host layout (same reason as the h2d strides: the
  // real plugin's default layout for rank>=3 is a permuted order)
  PJRT_Buffer_Dimensions_Args dim_args;
  std::memset(&dim_args, 0, sizeof(dim_args));
  dim_args.struct_size = PJRT_Buffer_Dimensions_Args_STRUCT_SIZE;
  dim_args.buffer = static_cast<PJRT_Buffer*>(buf);
  PJRT_Error* de = api->PJRT_Buffer_Dimensions(&dim_args);
  if (de != nullptr) {
    consume_error(api, de, err, errlen);
    return -1;
  }
  // row-major == minor_to_major [ndims-1, ..., 0], no tiles. Tiled is
  // the layout kind every PJRT plugin accepts on the ToHostBuffer path
  // (jaxlib's ToLiteral always passes Tiled; one plugin was seen to
  // reject Strides outright).
  std::vector<int64_t> m2m(dim_args.num_dims);
  for (size_t i = 0; i < dim_args.num_dims; ++i) {
    m2m[i] = static_cast<int64_t>(dim_args.num_dims - 1 - i);
  }
  PJRT_Buffer_MemoryLayout layout;
  std::memset(&layout, 0, sizeof(layout));
  layout.struct_size = PJRT_Buffer_MemoryLayout_STRUCT_SIZE;
  layout.type = PJRT_Buffer_MemoryLayout_Type_Tiled;
  layout.tiled.struct_size = PJRT_Buffer_MemoryLayout_Tiled_STRUCT_SIZE;
  layout.tiled.minor_to_major = m2m.empty() ? nullptr : m2m.data();
  layout.tiled.minor_to_major_size = m2m.size();

  PJRT_Buffer_ToHostBuffer_Args args;
  std::memset(&args, 0, sizeof(args));
  args.struct_size = PJRT_Buffer_ToHostBuffer_Args_STRUCT_SIZE;
  args.src = static_cast<PJRT_Buffer*>(buf);
  args.host_layout = &layout;
  args.dst = dst;
  args.dst_size = dst_size;
  PJRT_Error* e = api->PJRT_Buffer_ToHostBuffer(&args);
  if (e != nullptr) {
    consume_error(api, e, err, errlen);
    return -1;
  }
  if (dst == nullptr) {
    return static_cast<long long>(args.dst_size);
  }
  if (await_and_destroy(api, args.event, err, errlen) != 0) {
    return -1;
  }
  return static_cast<long long>(args.dst_size);
}

// Element dtype of a device buffer (PJRT_Buffer_Type enum value).
int dl4j_pjrt_buffer_dtype(const void* api_p, void* buf) {
  const PJRT_Api* api = static_cast<const PJRT_Api*>(api_p);
  PJRT_Buffer_ElementType_Args args;
  std::memset(&args, 0, sizeof(args));
  args.struct_size = PJRT_Buffer_ElementType_Args_STRUCT_SIZE;
  args.buffer = static_cast<PJRT_Buffer*>(buf);
  PJRT_Error* e = api->PJRT_Buffer_ElementType(&args);
  if (e != nullptr) {
    consume_error(api, e, nullptr, 0);
    return -1;
  }
  return static_cast<int>(args.type);
}

// Writes up to max_dims dimension sizes; returns ndims or -1.
int dl4j_pjrt_buffer_dims(const void* api_p, void* buf, int64_t* out_dims,
                          int max_dims) {
  const PJRT_Api* api = static_cast<const PJRT_Api*>(api_p);
  PJRT_Buffer_Dimensions_Args args;
  std::memset(&args, 0, sizeof(args));
  args.struct_size = PJRT_Buffer_Dimensions_Args_STRUCT_SIZE;
  args.buffer = static_cast<PJRT_Buffer*>(buf);
  PJRT_Error* e = api->PJRT_Buffer_Dimensions(&args);
  if (e != nullptr) {
    consume_error(api, e, nullptr, 0);
    return -1;
  }
  if (static_cast<int>(args.num_dims) > max_dims) {
    return -1;
  }
  for (size_t i = 0; i < args.num_dims; ++i) {
    out_dims[i] = args.dims[i];
  }
  return static_cast<int>(args.num_dims);
}

int dl4j_pjrt_buffer_destroy(const void* api_p, void* buf) {
  const PJRT_Api* api = static_cast<const PJRT_Api*>(api_p);
  PJRT_Buffer_Destroy_Args args;
  std::memset(&args, 0, sizeof(args));
  args.struct_size = PJRT_Buffer_Destroy_Args_STRUCT_SIZE;
  args.buffer = static_cast<PJRT_Buffer*>(buf);
  PJRT_Error* e = api->PJRT_Buffer_Destroy(&args);
  if (e != nullptr) {
    consume_error(api, e, nullptr, 0);
    return -1;
  }
  return 0;
}

// ---- execute ---------------------------------------------------------

// Single-device synchronous dispatch: run `lexec` on `num_args` input
// buffers; writes up to `max_outputs` output PJRT_Buffer* into
// `out_bufs`. Returns the number of outputs, or -1 (error in `err`).
int dl4j_pjrt_execute(const void* api_p, void* lexec, void** in_bufs,
                      int num_args, void** out_bufs, int max_outputs,
                      char* err, int errlen) {
  const PJRT_Api* api = static_cast<const PJRT_Api*>(api_p);
  int num_outputs = dl4j_pjrt_executable_num_outputs(api_p, lexec);
  if (num_outputs < 0) {
    set_err(err, errlen, "could not query executable output arity");
    return -1;
  }
  if (num_outputs > max_outputs) {
    set_err(err, errlen, "output buffer array too small");
    return -1;
  }

  PJRT_ExecuteOptions options;
  std::memset(&options, 0, sizeof(options));
  options.struct_size = PJRT_ExecuteOptions_STRUCT_SIZE;

  std::vector<PJRT_Buffer*> inputs(static_cast<size_t>(num_args));
  for (int i = 0; i < num_args; ++i) {
    inputs[static_cast<size_t>(i)] = static_cast<PJRT_Buffer*>(in_bufs[i]);
  }
  PJRT_Buffer* const* arg_list = inputs.data();
  std::vector<PJRT_Buffer*> outputs(static_cast<size_t>(num_outputs),
                                    nullptr);
  PJRT_Buffer** out_list = outputs.data();
  PJRT_Event* device_complete = nullptr;

  PJRT_LoadedExecutable_Execute_Args args;
  std::memset(&args, 0, sizeof(args));
  args.struct_size = PJRT_LoadedExecutable_Execute_Args_STRUCT_SIZE;
  args.executable = static_cast<PJRT_LoadedExecutable*>(lexec);
  args.options = &options;
  args.argument_lists = &arg_list;
  args.num_devices = 1;
  args.num_args = static_cast<size_t>(num_args);
  args.output_lists = &out_list;
  args.device_complete_events = &device_complete;
  PJRT_Error* e = api->PJRT_LoadedExecutable_Execute(&args);
  if (e != nullptr) {
    consume_error(api, e, err, errlen);
    return -1;
  }
  if (await_and_destroy(api, device_complete, err, errlen) != 0) {
    return -1;
  }
  for (int i = 0; i < num_outputs; ++i) {
    out_bufs[i] = outputs[static_cast<size_t>(i)];
  }
  return num_outputs;
}

// ---------------------------------------------------------------------------
// Executable cache — keyed compilation (SURVEY §7 "hard parts":
// "executable caching keyed on shapes"). The key is caller-provided
// (the host API uses the program's shape signature), the value a
// PJRT_LoadedExecutable* owned by the cache until destroy.
// ---------------------------------------------------------------------------

struct Dl4jExecCache {
  std::mutex mu;
  std::unordered_map<std::string, void*> map;
  const void* api;
};

void* dl4j_exec_cache_create(const void* api_p) {
  auto* c = new Dl4jExecCache();
  c->api = api_p;
  return c;
}

// Returns the cached executable or compiles + inserts (one compile per
// key even under concurrent callers). hits/misses are reported via the
// out_hit flag so the host can track cache effectiveness.
void* dl4j_exec_cache_get_or_compile(const void* api_p, void* client,
                                     void* cache_p, const char* key,
                                     const char* mlir, size_t mlir_size,
                                     int* out_hit, char* err,
                                     int errlen) {
  auto* cache = static_cast<Dl4jExecCache*>(cache_p);
  {
    std::lock_guard<std::mutex> lock(cache->mu);
    auto it = cache->map.find(key);
    if (it != cache->map.end()) {
      if (out_hit != nullptr) *out_hit = 1;
      return it->second;
    }
  }
  if (out_hit != nullptr) *out_hit = 0;
  void* exec = dl4j_pjrt_compile_mlir(api_p, client, mlir, mlir_size,
                                      nullptr, 0, err, errlen);
  if (exec == nullptr) return nullptr;
  std::lock_guard<std::mutex> lock(cache->mu);
  auto it = cache->map.find(key);
  if (it != cache->map.end()) {
    // lost a compile race: keep the first entry, drop ours
    dl4j_pjrt_executable_destroy(api_p, exec);
    return it->second;
  }
  cache->map.emplace(key, exec);
  return exec;
}

int dl4j_exec_cache_size(void* cache_p) {
  auto* cache = static_cast<Dl4jExecCache*>(cache_p);
  std::lock_guard<std::mutex> lock(cache->mu);
  return static_cast<int>(cache->map.size());
}

int dl4j_exec_cache_destroy(const void* api_p, void* cache_p) {
  auto* cache = static_cast<Dl4jExecCache*>(cache_p);
  int rc = 0;
  for (auto& kv : cache->map) {
    if (dl4j_pjrt_executable_destroy(api_p, kv.second) != 0) rc = -1;
  }
  delete cache;
  return rc;
}

// ---------------------------------------------------------------------------
// Async executor — a native dispatch queue so the host thread can
// enqueue steps and overlap Python-side work (data prep, logging) with
// device execution; the libnd4j-flush analog of ND4J's async op queue.
// One worker thread executes submissions FIFO (PJRT execution itself
// is async on-device; this queue removes the host dispatch+await from
// the caller's thread).
// ---------------------------------------------------------------------------

struct Dl4jAsyncTask {
  long long ticket;
  void* lexec;
  std::vector<void*> inputs;
  bool done = false;
  int num_outputs = -1;
  std::vector<void*> outputs;
  std::string error;
};

struct Dl4jAsyncExecutor {
  const void* api;
  std::mutex mu;
  std::condition_variable cv;
  std::deque<Dl4jAsyncTask*> pending;
  std::unordered_map<long long, Dl4jAsyncTask*> tasks;
  long long next_ticket = 1;
  bool shutting_down = false;
  std::thread worker;
};

void* dl4j_async_create(const void* api_p) {
  auto* ex = new Dl4jAsyncExecutor();
  ex->api = api_p;
  ex->worker = std::thread([ex]() {
    for (;;) {
      Dl4jAsyncTask* task = nullptr;
      {
        std::unique_lock<std::mutex> lock(ex->mu);
        ex->cv.wait(lock, [ex]() {
          return ex->shutting_down || !ex->pending.empty();
        });
        if (ex->pending.empty()) return;  // shutdown + drained
        task = ex->pending.front();
        ex->pending.pop_front();
      }
      char err[512] = {0};
      std::vector<void*> outs(64, nullptr);
      int n = dl4j_pjrt_execute(ex->api, task->lexec,
                                task->inputs.data(),
                                static_cast<int>(task->inputs.size()),
                                outs.data(),
                                static_cast<int>(outs.size()), err,
                                sizeof(err));
      {
        std::lock_guard<std::mutex> lock(ex->mu);
        task->num_outputs = n;
        if (n < 0) {
          task->error = err;
        } else {
          task->outputs.assign(outs.begin(), outs.begin() + n);
        }
        task->done = true;
      }
      ex->cv.notify_all();
    }
  });
  return ex;
}

long long dl4j_async_submit(void* ex_p, void* lexec, void** in_bufs,
                            int num_args) {
  auto* ex = static_cast<Dl4jAsyncExecutor*>(ex_p);
  auto* task = new Dl4jAsyncTask();
  task->lexec = lexec;
  task->inputs.assign(in_bufs, in_bufs + num_args);
  long long ticket;
  {
    std::lock_guard<std::mutex> lock(ex->mu);
    if (ex->shutting_down) {
      delete task;
      return -1;
    }
    ticket = ex->next_ticket++;
    task->ticket = ticket;
    ex->tasks.emplace(ticket, task);
    ex->pending.push_back(task);
  }
  ex->cv.notify_all();
  return ticket;
}

// Blocks until the ticket's execution finishes; fills out_bufs and
// removes the task. Returns output count or -1 (error text in err).
int dl4j_async_wait(void* ex_p, long long ticket, void** out_bufs,
                    int max_outputs, char* err, int errlen) {
  auto* ex = static_cast<Dl4jAsyncExecutor*>(ex_p);
  Dl4jAsyncTask* task = nullptr;
  {
    std::unique_lock<std::mutex> lock(ex->mu);
    auto it = ex->tasks.find(ticket);
    if (it == ex->tasks.end()) {
      set_err(err, errlen, "unknown ticket");
      return -1;
    }
    task = it->second;
    ex->cv.wait(lock, [task]() { return task->done; });
    ex->tasks.erase(it);
  }
  int n = task->num_outputs;
  if (n < 0) {
    set_err(err, errlen, task->error.c_str());
  } else if (n > max_outputs) {
    // free the materialized device buffers before failing, or they
    // leak HBM with no handle left to reclaim them
    for (void* b : task->outputs) dl4j_pjrt_buffer_destroy(ex->api, b);
    set_err(err, errlen, "output buffer array too small");
    n = -1;
  } else {
    for (int i = 0; i < n; ++i) out_bufs[i] = task->outputs[i];
  }
  delete task;
  return n;
}

int dl4j_async_destroy(void* ex_p) {
  auto* ex = static_cast<Dl4jAsyncExecutor*>(ex_p);
  {
    std::lock_guard<std::mutex> lock(ex->mu);
    ex->shutting_down = true;
  }
  ex->cv.notify_all();
  if (ex->worker.joinable()) ex->worker.join();
  // any never-waited tasks leak their output buffers by design (the
  // caller owns buffer lifetime); free task records only
  for (auto& kv : ex->tasks) delete kv.second;
  delete ex;
  return 0;
}

}  // extern "C"
