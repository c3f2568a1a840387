// NumPy allocation handler (NEP 49) that gives the host arrays of
// device-to-host copies recycled slabs: memory a copy has written before,
// whose pages the process has faulted in already.
//
// jaxlib allocates the host array of ``np.asarray(jax_array)`` through
// numpy's allocator handler, on the calling thread, with the GIL held, and
// numpy keeps the handler in a context variable. So a staging thread sets
// this handler around that one call (ts_slabs_enter / ts_slabs_exit) and
// the runtime's copy lands in a slab. An allocation of at least the floor
// asks Python (the ``take`` callback of ts_slabs_init, d2h_slabs.py) for a
// slab of exactly its bytes; everything else, and every request Python
// declines, goes to numpy's default handler. The array keeps the handler
// that allocated it, so its slab comes back (the ``give`` callback) when it
// is freed, on whatever thread that happens.
//
// Built and loaded by _native.py (slab_alloc) with ctypes.PyDLL: every
// entry point runs with the GIL held.

#define PY_SSIZE_T_CLEAN
#include <Python.h>
#define NPY_TARGET_VERSION NPY_1_22_API_VERSION
#define NPY_NO_DEPRECATED_API NPY_1_22_API_VERSION
#include <numpy/arrayobject.h>

#include <cstring>
#include <mutex>
#include <unordered_map>

namespace {

PyObject* g_take = nullptr;  // take(nbytes) -> a slab's address, or 0
PyObject* g_give = nullptr;  // give(address): the slab is free again
size_t g_floor = 0;
PyDataMemAllocator* g_default = nullptr;
PyObject* g_capsule = nullptr;

// Slabs under a numpy array: address -> bytes. Whether a pointer numpy
// frees is a slab is answered here, without Python.
std::mutex g_mu;
std::unordered_map<void*, size_t> g_out;

// The handler this thread had before ts_slabs_enter.
thread_local PyObject* tl_prev = nullptr;

bool python_alive() {
#if PY_VERSION_HEX >= 0x030D0000
  return !Py_IsFinalizing();
#else
  return !_Py_IsFinalizing();
#endif
}

// A slab of exactly ``size`` bytes from Python, or nullptr. The callback
// runs under numpy's default handler: a slab it makes is an array of its
// own, which must not come back here.
void* take(size_t size) {
  if (g_take == nullptr || !python_alive()) return nullptr;
  PyGILState_STATE gil = PyGILState_Ensure();
  PyObject *type, *value, *tb;
  PyErr_Fetch(&type, &value, &tb);
  void* p = nullptr;
  PyObject* mine = PyDataMem_SetHandler(nullptr);
  if (mine != nullptr) {
    PyObject* r = PyObject_CallFunction(g_take, "n", (Py_ssize_t)size);
    if (r != nullptr) {
      p = PyLong_AsVoidPtr(r);
      Py_DECREF(r);
    }
    if (PyErr_Occurred()) {
      PyErr_WriteUnraisable(g_take);
      p = nullptr;
    }
    Py_XDECREF(PyDataMem_SetHandler(mine));
    Py_DECREF(mine);
  }
  PyErr_Clear();
  PyErr_Restore(type, value, tb);
  if (p != nullptr) {
    try {
      std::lock_guard<std::mutex> lock(g_mu);
      g_out[p] = size;
    } catch (...) {
      // Untracked it would be freed as numpy's own: hand it back now.
      PyObject* r = PyObject_CallFunction(g_give, "N", PyLong_FromVoidPtr(p));
      Py_XDECREF(r);
      PyErr_Clear();
      p = nullptr;
    }
  }
  PyGILState_Release(gil);
  return p;
}

// True where ``p`` is a slab (now forgotten here), with its bytes.
bool forget(void* p, size_t* size) {
  std::lock_guard<std::mutex> lock(g_mu);
  auto it = g_out.find(p);
  if (it == g_out.end()) return false;
  *size = it->second;
  g_out.erase(it);
  return true;
}

void give(void* p) {
  // At exit the slab goes with the process.
  if (!python_alive()) return;
  PyGILState_STATE gil = PyGILState_Ensure();
  PyObject *type, *value, *tb;
  PyErr_Fetch(&type, &value, &tb);
  PyObject* r = PyObject_CallFunction(g_give, "N", PyLong_FromVoidPtr(p));
  if (r == nullptr) PyErr_WriteUnraisable(g_give);
  Py_XDECREF(r);
  PyErr_Restore(type, value, tb);
  PyGILState_Release(gil);
}

void* slab_malloc(void*, size_t size) {
  if (size >= g_floor) {
    void* p = take(size);
    if (p != nullptr) return p;
  }
  return g_default->malloc(g_default->ctx, size);
}

void* slab_calloc(void*, size_t nelem, size_t elsize) {
  size_t size;
  if (!__builtin_mul_overflow(nelem, elsize, &size) && size >= g_floor) {
    void* p = take(size);
    if (p != nullptr) return std::memset(p, 0, size);
  }
  return g_default->calloc(g_default->ctx, nelem, elsize);
}

void* slab_realloc(void*, void* ptr, size_t new_size) {
  size_t old_size;
  if (ptr != nullptr && forget(ptr, &old_size)) {
    void* q = g_default->malloc(g_default->ctx, new_size);
    if (q == nullptr) {
      std::lock_guard<std::mutex> lock(g_mu);
      g_out[ptr] = old_size;
      return nullptr;
    }
    std::memcpy(q, ptr, old_size < new_size ? old_size : new_size);
    give(ptr);
    return q;
  }
  return g_default->realloc(g_default->ctx, ptr, new_size);
}

void slab_free(void*, void* ptr, size_t size) {
  size_t slab_size;
  if (ptr != nullptr && forget(ptr, &slab_size)) {
    give(ptr);
    return;
  }
  g_default->free(g_default->ctx, ptr, size);
}

PyDataMem_Handler g_handler = {
    "torchsnapshot_tpu_d2h_slabs",
    1,
    {nullptr, slab_malloc, slab_calloc, slab_realloc, slab_free},
};

}  // namespace

extern "C" {

// Once a process: the callbacks and the floor. 0, or -1 where numpy's
// C API is not there (nothing is set then).
int ts_slabs_init(PyObject* take_cb, PyObject* give_cb, Py_ssize_t floor) {
  if (g_capsule != nullptr) return 0;
  if (_import_array() < 0) {
    PyErr_Clear();
    return -1;
  }
  PyDataMem_Handler* def = static_cast<PyDataMem_Handler*>(
      PyCapsule_GetPointer(PyDataMem_DefaultHandler, "mem_handler"));
  if (def == nullptr) {
    PyErr_Clear();
    return -1;
  }
  PyObject* capsule = PyCapsule_New(&g_handler, "mem_handler", nullptr);
  if (capsule == nullptr) {
    PyErr_Clear();
    return -1;
  }
  g_default = &def->allocator;
  g_floor = static_cast<size_t>(floor);
  Py_INCREF(take_cb);
  Py_INCREF(give_cb);
  g_take = take_cb;
  g_give = give_cb;
  g_capsule = capsule;
  return 0;
}

// This thread's numpy allocations, in this context, go through the slab
// handler until ts_slabs_exit (the two do not nest). 0, or -1 where the
// handler could not be set.
int ts_slabs_enter(void) {
  if (g_capsule == nullptr || tl_prev != nullptr) return -1;
  tl_prev = PyDataMem_SetHandler(g_capsule);
  if (tl_prev == nullptr) {
    PyErr_Clear();
    return -1;
  }
  return 0;
}

void ts_slabs_exit(void) {
  PyObject* prev = tl_prev;
  if (prev == nullptr) return;
  tl_prev = nullptr;
  PyObject* mine = PyDataMem_SetHandler(prev);
  if (mine == nullptr) PyErr_Clear();
  Py_XDECREF(mine);
  Py_DECREF(prev);
}

}  // extern "C"
