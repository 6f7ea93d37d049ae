// pycpu_pjrt — a CPU PJRT plugin for CI and chip-less machines.
//
// The image ships no standalone CPU PJRT plugin .so (jaxlib's CPU client is
// statically linked into its Python extension), so the C++ serving path
// (pt_predictor: dlopen -> PJRT C API -> compile -> execute -> readback)
// could only ever run against live TPU hardware. This plugin closes that
// gap: it exports the PJRT C API surface pt_predictor uses and delegates
// compilation/execution of the StableHLO program to jax's CPU runtime
// through an embedded CPython interpreter.
//
// This keeps the e2e predictor regressions always-on (ref: the reference's
// /root/reference/paddle/fluid/inference/tests/api/ CPU regressions run on
// every build), exercising the exact same C++ client code that drives the
// TPU plugin in production. It is a correctness/CI backend, not a
// performance path: buffers live host-side as numpy arrays and hop through
// jax per execution.
//
// Contract notes (matching predictor.cc's usage):
//   * all operations are synchronous; event out-params are left null and
//     Event_Await/Destroy accept null events
//   * ToHostBuffer with dst == null is a size query (sets dst_size)
//   * GetExecutable returns the same underlying object as the loaded
//     executable; NumOutputs is captured at compile time
//     (len(exe.get_output_layouts()))
//
// Environment: honors PYTHONPATH (set it to the venv's site-packages when
// the hosting process is not the venv python). Forces JAX_PLATFORMS=cpu: this
// plugin never claims a chip.

#include <Python.h>
#include <dlfcn.h>

#include <cstdint>
#include <cstdlib>
#include <cstring>
#include <string>
#include <vector>

#include "xla/pjrt/c/pjrt_c_api.h"

// Incomplete PJRT types get their definitions here.
struct PJRT_Error {
  std::string message;
};

struct PJRT_Client {
  PyObject* helper;  // module with compile/from_bytes/execute/to_bytes
};

struct PJRT_Buffer {
  PyObject* arr;               // numpy array (owned)
  std::vector<int64_t> dims;   // cached for PJRT_Buffer_Dimensions
  PJRT_Buffer_Type type;
  size_t nbytes;
};

struct PJRT_LoadedExecutable {
  PyObject* exe;  // jaxlib LoadedExecutable (owned)
  size_t num_outputs;
};

struct PJRT_Device {};      // one static CPU device
struct PJRT_Event {};       // never instantiated (synchronous plugin)
struct PJRT_Executable;     // alias of PJRT_LoadedExecutable (same object)

namespace {

PJRT_Device g_device;
PJRT_Device* g_device_ptr = &g_device;

PJRT_Error* MakeError(const std::string& msg) {
  auto* e = new PJRT_Error;
  e->message = msg;
  return e;
}

PJRT_Error* PyError(const char* what) {
  PyObject *type = nullptr, *value = nullptr, *tb = nullptr;
  PyErr_Fetch(&type, &value, &tb);
  PyErr_NormalizeException(&type, &value, &tb);
  std::string msg = std::string("pycpu_pjrt ") + what + ": ";
  if (value) {
    PyObject* s = PyObject_Str(value);
    if (s) {
      msg += PyUnicode_AsUTF8(s);
      Py_DECREF(s);
    }
  }
  Py_XDECREF(type);
  Py_XDECREF(value);
  Py_XDECREF(tb);
  return MakeError(msg);
}

const char* kHelperSrc = R"PY(
import os
import sys
# pin the platform INSIDE Python too: in a host-Python process (ctypes
# C-API callers) the interpreter's os.environ snapshot predates our C
# setenv call, and `import jax` must not reach for an accelerator
os.environ["JAX_PLATFORMS"] = "cpu"
try:
    import numpy as np
except Exception as _e:
    raise ImportError(
        f"numpy import failed in embedded interpreter: {_e!r} "
        f"[sys.prefix={sys.prefix} sys.path={sys.path}]") from _e
import jax
# note: _dev below selects the CPU backend EXPLICITLY (jax.devices('cpu')),
# so a host that already imported jax against another platform still works
from jax._src.lib import xla_client
from jaxlib._jax import DeviceList
import ml_dtypes

_dev = jax.devices('cpu')[0]
_backend = _dev.client
# exactly one device, even when the host env forces a multi-device CPU
# platform (e.g. a test runner's --xla_force_host_platform_device_count)
_dl = DeviceList((_dev,))

_DTYPES = {
    'bool': np.dtype(np.bool_), 'int8': np.dtype(np.int8),
    'int16': np.dtype(np.int16), 'int32': np.dtype(np.int32),
    'int64': np.dtype(np.int64), 'uint8': np.dtype(np.uint8),
    'uint16': np.dtype(np.uint16), 'uint32': np.dtype(np.uint32),
    'uint64': np.dtype(np.uint64), 'float16': np.dtype(np.float16),
    'float32': np.dtype(np.float32), 'float64': np.dtype(np.float64),
    'bfloat16': np.dtype(ml_dtypes.bfloat16),
}


def compile_program(text):
    exe = _backend.compile_and_load(text, _dl, xla_client.CompileOptions())
    return exe, len(exe.get_output_layouts())


def from_bytes(data, dtype_name, dims):
    return np.frombuffer(data, dtype=_DTYPES[dtype_name]).reshape(dims).copy()


def to_bytes(arr):
    return np.ascontiguousarray(arr).tobytes()


def execute(exe, arrs):
    bufs = [_backend.buffer_from_pyval(a, _dev) for a in arrs]
    outs = exe.execute_sharded(bufs)
    return [np.asarray(a[0])
            for a in outs.disassemble_into_single_device_arrays()]


def dtype_name(arr):
    d = arr.dtype
    for name, dt in _DTYPES.items():
        if d == dt:
            return name
    raise TypeError(f'unsupported dtype {d}')
)PY";

const char* DtypeName(PJRT_Buffer_Type t) {
  switch (t) {
    case PJRT_Buffer_Type_PRED: return "bool";
    case PJRT_Buffer_Type_S8: return "int8";
    case PJRT_Buffer_Type_S16: return "int16";
    case PJRT_Buffer_Type_S32: return "int32";
    case PJRT_Buffer_Type_S64: return "int64";
    case PJRT_Buffer_Type_U8: return "uint8";
    case PJRT_Buffer_Type_U16: return "uint16";
    case PJRT_Buffer_Type_U32: return "uint32";
    case PJRT_Buffer_Type_U64: return "uint64";
    case PJRT_Buffer_Type_F16: return "float16";
    case PJRT_Buffer_Type_F32: return "float32";
    case PJRT_Buffer_Type_F64: return "float64";
    case PJRT_Buffer_Type_BF16: return "bfloat16";
    default: return nullptr;
  }
}

PJRT_Buffer_Type TypeFromName(const std::string& n) {
  if (n == "bool") return PJRT_Buffer_Type_PRED;
  if (n == "int8") return PJRT_Buffer_Type_S8;
  if (n == "int16") return PJRT_Buffer_Type_S16;
  if (n == "int32") return PJRT_Buffer_Type_S32;
  if (n == "int64") return PJRT_Buffer_Type_S64;
  if (n == "uint8") return PJRT_Buffer_Type_U8;
  if (n == "uint16") return PJRT_Buffer_Type_U16;
  if (n == "uint32") return PJRT_Buffer_Type_U32;
  if (n == "uint64") return PJRT_Buffer_Type_U64;
  if (n == "float16") return PJRT_Buffer_Type_F16;
  if (n == "float32") return PJRT_Buffer_Type_F32;
  if (n == "float64") return PJRT_Buffer_Type_F64;
  if (n == "bfloat16") return PJRT_Buffer_Type_BF16;
  return PJRT_Buffer_Type_INVALID;
}

PyObject* g_helper = nullptr;
PJRT_Client g_client;

// RAII GIL guard: the host may be a live Python process whose ctypes
// call released the GIL (the C-API e2e tests), a plain C++ process where
// we initialized Python ourselves, or any thread of either. After
// EnsurePython() releases the init thread state, PyGILState_Ensure is
// uniformly correct everywhere.
struct GilGuard {
  PyGILState_STATE st;
  bool active;
  GilGuard() : active(Py_IsInitialized() != 0) {
    if (active) st = PyGILState_Ensure();
  }
  ~GilGuard() {
    if (active) PyGILState_Release(st);
  }
};

PJRT_Error* EnsurePython() {
  if (g_helper != nullptr) return nullptr;
  setenv("JAX_PLATFORMS", "cpu", 1);
  // The host dlopens this plugin RTLD_LOCAL, so libpython arrives with
  // local visibility — but numpy/jaxlib C extensions resolve Python ABI
  // symbols through the global table. Promote libpython to RTLD_GLOBAL
  // (NOLOAD: it is already mapped as our dependency).
  if (!dlopen("libpython3.12.so.1.0",
              RTLD_NOW | RTLD_GLOBAL | RTLD_NOLOAD)) {
    dlopen("libpython3.12.so", RTLD_NOW | RTLD_GLOBAL | RTLD_NOLOAD);
  }
  bool we_initialized = false;
  if (!Py_IsInitialized()) {
    Py_InitializeEx(0);
    we_initialized = true;
  }
  PyGILState_STATE st = PyGILState_Ensure();
  PyObject* mod = PyModule_New("pycpu_helper");
  // on any failure: balance the ensure AND, when we initialized Python
  // ourselves, hand back the init thread's GIL — otherwise the caller
  // keeps it forever and every later GilGuard deadlocks
  auto fail = [&](PJRT_Error* e) {
    PyGILState_Release(st);
    if (we_initialized) PyEval_SaveThread();
    return e;
  };
  if (!mod) return fail(PyError("module"));
  PyObject* dict = PyModule_GetDict(mod);
  PyDict_SetItemString(dict, "__builtins__", PyEval_GetBuiltins());
  PyObject* res = PyRun_String(kHelperSrc, Py_file_input, dict, dict);
  if (!res) {
    Py_DECREF(mod);
    return fail(PyError("helper init (is PYTHONPATH set to the venv "
                        "site-packages?)"));
  }
  Py_DECREF(res);
  g_helper = mod;
  PyGILState_Release(st);
  if (we_initialized) {
    // release the GIL the init thread implicitly holds so that all entry
    // points (from any thread) can PyGILState_Ensure symmetrically
    PyEval_SaveThread();
  }
  return nullptr;
}

PyObject* Call(const char* fn, PyObject* args, PJRT_Error** err,
               const char* what) {
  PyObject* f = PyObject_GetAttrString(g_helper, fn);
  if (!f) {
    *err = PyError(what);
    return nullptr;
  }
  PyObject* r = PyObject_CallObject(f, args);
  Py_DECREF(f);
  if (!r) *err = PyError(what);
  return r;
}

PJRT_Buffer* WrapArray(PyObject* arr, PJRT_Error** err) {
  // arr: new reference to a numpy array; ownership moves into the buffer
  PJRT_Error* e = nullptr;
  PyObject* args = Py_BuildValue("(O)", arr);
  PyObject* name = Call("dtype_name", args, &e, "dtype_name");
  Py_DECREF(args);
  if (!name) {
    *err = e;
    Py_DECREF(arr);
    return nullptr;
  }
  auto* b = new PJRT_Buffer;
  b->arr = arr;
  b->type = TypeFromName(PyUnicode_AsUTF8(name));
  Py_DECREF(name);
  PyObject* shape = PyObject_GetAttrString(arr, "shape");
  Py_ssize_t nd = PyTuple_Size(shape);
  for (Py_ssize_t i = 0; i < nd; ++i)
    b->dims.push_back(PyLong_AsLongLong(PyTuple_GetItem(shape, i)));
  Py_DECREF(shape);
  PyObject* nb = PyObject_GetAttrString(arr, "nbytes");
  b->nbytes = static_cast<size_t>(PyLong_AsSize_t(nb));
  Py_DECREF(nb);
  return b;
}

// ---- PJRT C API implementations -------------------------------------

void ErrorDestroy(PJRT_Error_Destroy_Args* args) {
  delete args->error;
}

void ErrorMessage(PJRT_Error_Message_Args* args) {
  args->message = args->error->message.c_str();
  args->message_size = args->error->message.size();
}

PJRT_Error* ClientCreate(PJRT_Client_Create_Args* args) {
  PJRT_Error* e = EnsurePython();
  if (e) return e;
  g_client.helper = g_helper;
  args->client = &g_client;
  return nullptr;
}

PJRT_Error* ClientAddressableDevices(
    PJRT_Client_AddressableDevices_Args* args) {
  args->addressable_devices = &g_device_ptr;
  args->num_addressable_devices = 1;
  return nullptr;
}

PJRT_Error* ClientCompile(PJRT_Client_Compile_Args* args) {
  GilGuard gil;
  PJRT_Error* e = nullptr;
  PyObject* text = PyUnicode_FromStringAndSize(args->program->code,
                                               args->program->code_size);
  if (!text) return PyError("program text");
  PyObject* targs = Py_BuildValue("(O)", text);
  Py_DECREF(text);
  PyObject* r = Call("compile_program", targs, &e, "compile");
  Py_DECREF(targs);
  if (!r) return e;
  auto* exe = new PJRT_LoadedExecutable;
  exe->exe = PyTuple_GetItem(r, 0);
  Py_INCREF(exe->exe);
  exe->num_outputs = PyLong_AsSize_t(PyTuple_GetItem(r, 1));
  Py_DECREF(r);
  args->executable = exe;
  return nullptr;
}

PJRT_Error* BufferFromHostBuffer(
    PJRT_Client_BufferFromHostBuffer_Args* args) {
  GilGuard gil;
  const char* dname = DtypeName(args->type);
  if (!dname)
    return MakeError("unsupported PJRT_Buffer_Type " +
                     std::to_string(static_cast<int>(args->type)));
  size_t elems = 1;
  for (size_t i = 0; i < args->num_dims; ++i)
    elems *= static_cast<size_t>(args->dims[i]);
  size_t esize;
  switch (args->type) {
    case PJRT_Buffer_Type_PRED: case PJRT_Buffer_Type_S8:
    case PJRT_Buffer_Type_U8: esize = 1; break;
    case PJRT_Buffer_Type_S16: case PJRT_Buffer_Type_U16:
    case PJRT_Buffer_Type_F16: case PJRT_Buffer_Type_BF16: esize = 2; break;
    case PJRT_Buffer_Type_S64: case PJRT_Buffer_Type_U64:
    case PJRT_Buffer_Type_F64: esize = 8; break;
    default: esize = 4;
  }
  PyObject* data = PyBytes_FromStringAndSize(
      static_cast<const char*>(args->data),
      static_cast<Py_ssize_t>(elems * esize));
  PyObject* dims = PyTuple_New(static_cast<Py_ssize_t>(args->num_dims));
  for (size_t i = 0; i < args->num_dims; ++i)
    PyTuple_SetItem(dims, static_cast<Py_ssize_t>(i),
                    PyLong_FromLongLong(args->dims[i]));
  PJRT_Error* e = nullptr;
  PyObject* targs = Py_BuildValue("(OsO)", data, dname, dims);
  Py_DECREF(data);
  Py_DECREF(dims);
  PyObject* arr = Call("from_bytes", targs, &e, "from_bytes");
  Py_DECREF(targs);
  if (!arr) return e;
  PJRT_Buffer* b = WrapArray(arr, &e);
  if (!b) return e;
  args->buffer = b;
  args->done_with_host_buffer = nullptr;  // synchronous copy
  return nullptr;
}

PJRT_Error* LoadedExecutableGetExecutable(
    PJRT_LoadedExecutable_GetExecutable_Args* args) {
  args->executable =
      reinterpret_cast<PJRT_Executable*>(args->loaded_executable);
  return nullptr;
}

PJRT_Error* ExecutableNumOutputs(PJRT_Executable_NumOutputs_Args* args) {
  args->num_outputs =
      reinterpret_cast<PJRT_LoadedExecutable*>(args->executable)
          ->num_outputs;
  return nullptr;
}

PJRT_Error* LoadedExecutableExecute(
    PJRT_LoadedExecutable_Execute_Args* args) {
  GilGuard gil;
  if (args->num_devices != 1)
    return MakeError("pycpu_pjrt supports exactly one device");
  PJRT_Error* e = nullptr;
  PyObject* lst = PyList_New(static_cast<Py_ssize_t>(args->num_args));
  for (size_t i = 0; i < args->num_args; ++i) {
    PyObject* a = args->argument_lists[0][i]->arr;
    Py_INCREF(a);
    PyList_SetItem(lst, static_cast<Py_ssize_t>(i), a);
  }
  PyObject* targs = Py_BuildValue("(OO)", args->executable->exe, lst);
  Py_DECREF(lst);
  PyObject* outs = Call("execute", targs, &e, "execute");
  Py_DECREF(targs);
  if (!outs) return e;
  Py_ssize_t n = PyList_Size(outs);
  for (Py_ssize_t i = 0; i < n; ++i) {
    PyObject* a = PyList_GetItem(outs, i);
    Py_INCREF(a);
    PJRT_Buffer* b = WrapArray(a, &e);
    if (!b) {
      Py_DECREF(outs);
      return e;
    }
    args->output_lists[0][i] = b;
  }
  Py_DECREF(outs);
  if (args->device_complete_events)
    args->device_complete_events[0] = nullptr;  // synchronous
  return nullptr;
}

PJRT_Error* BufferToHostBuffer(PJRT_Buffer_ToHostBuffer_Args* args) {
  GilGuard gil;
  PJRT_Buffer* b = args->src;
  if (args->dst == nullptr) {  // size query
    args->dst_size = b->nbytes;
    return nullptr;
  }
  PJRT_Error* e = nullptr;
  PyObject* targs = Py_BuildValue("(O)", b->arr);
  PyObject* bytes = Call("to_bytes", targs, &e, "to_bytes");
  Py_DECREF(targs);
  if (!bytes) return e;
  size_t n = static_cast<size_t>(PyBytes_Size(bytes));
  if (n > args->dst_size) {
    Py_DECREF(bytes);
    return MakeError("dst_size too small");
  }
  memcpy(args->dst, PyBytes_AsString(bytes), n);
  Py_DECREF(bytes);
  args->event = nullptr;  // synchronous
  return nullptr;
}

PJRT_Error* BufferDimensions(PJRT_Buffer_Dimensions_Args* args) {
  args->dims = args->buffer->dims.data();
  args->num_dims = args->buffer->dims.size();
  return nullptr;
}

PJRT_Error* BufferElementType(PJRT_Buffer_ElementType_Args* args) {
  args->type = args->buffer->type;
  return nullptr;
}

PJRT_Error* BufferDestroy(PJRT_Buffer_Destroy_Args* args) {
  GilGuard gil;
  Py_XDECREF(args->buffer->arr);
  delete args->buffer;
  return nullptr;
}

PJRT_Error* EventAwait(PJRT_Event_Await_Args* args) {
  return nullptr;  // all ops synchronous; null events are already done
}

PJRT_Error* EventDestroy(PJRT_Event_Destroy_Args* args) {
  return nullptr;
}

PJRT_Api g_api;

}  // namespace

extern "C" const PJRT_Api* GetPjrtApi() {
  memset(&g_api, 0, sizeof(g_api));
  g_api.struct_size = PJRT_Api_STRUCT_SIZE;
  g_api.pjrt_api_version.major_version = PJRT_API_MAJOR;
  g_api.pjrt_api_version.minor_version = PJRT_API_MINOR;
  g_api.PJRT_Error_Destroy = ErrorDestroy;
  g_api.PJRT_Error_Message = ErrorMessage;
  g_api.PJRT_Client_Create = ClientCreate;
  g_api.PJRT_Client_AddressableDevices = ClientAddressableDevices;
  g_api.PJRT_Client_Compile = ClientCompile;
  g_api.PJRT_Client_BufferFromHostBuffer = BufferFromHostBuffer;
  g_api.PJRT_LoadedExecutable_GetExecutable = LoadedExecutableGetExecutable;
  g_api.PJRT_Executable_NumOutputs = ExecutableNumOutputs;
  g_api.PJRT_LoadedExecutable_Execute = LoadedExecutableExecute;
  g_api.PJRT_Buffer_ToHostBuffer = BufferToHostBuffer;
  g_api.PJRT_Buffer_Dimensions = BufferDimensions;
  g_api.PJRT_Buffer_ElementType = BufferElementType;
  g_api.PJRT_Buffer_Destroy = BufferDestroy;
  g_api.PJRT_Event_Await = EventAwait;
  g_api.PJRT_Event_Destroy = EventDestroy;
  return &g_api;
}
